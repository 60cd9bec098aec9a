"""FLOPs of one ``flux-dev`` image-step, counted from shapes.

``dense`` is every matrix product whose work no implementation may skip:
the image, text, timestep and pooled-vector embeddings; in each
double-stream block both streams' modulation, fused qkv, output
projection and MLP; in each single-stream block its modulation and fused
input and output projections; and the output head.  ``attention`` is the
dense count of QK^T and PV, which TimeRipple's reuse may legitimately skip
part of; it is reported beside ``dense`` and is never part of ``mfu``.
A multiply-add counts as two operations.
"""

POOLED = 768


def latent_tokens(m):
    return (m["latent_res"] // m["patch"]) ** 2


def step_flops(m):
    d, L = m["d_model"], m["txt_tokens"]
    F = int(d * m["mlp_ratio"])
    n = latent_tokens(m)
    N = n + L
    pin = m["patch"] ** 2 * m["in_channels"]
    embed = 2 * n * pin * d + 2 * L * m["txt_dim"] * d + 2 * 256 * d \
        + 2 * d * d + 2 * POOLED * d
    double = sum(2 * d * 6 * d + 2 * T * d * (3 * d + d + 2 * F)
                 for T in (L, n))
    single = 2 * d * 3 * d + 2 * N * d * (3 * d + F) + 2 * N * (d + F) * d
    head = 2 * d * 2 * d + 2 * n * d * pin
    layers = m["n_double_blocks"] + m["n_single_blocks"]
    return {"dense": embed + m["n_double_blocks"] * double
            + m["n_single_blocks"] * single + head,
            "attention": layers * 4 * N * N * d}
