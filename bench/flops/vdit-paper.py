"""FLOPs of one ``vdit-paper`` request-step, counted from shapes.

``dense`` is every matrix product whose work no implementation may skip:
the patch, text and timestep embeddings, each block's adaLN projection,
q/k/v/output projections and gated MLP, and the output head.  ``attention``
is the dense count of QK^T and PV, which TimeRipple's reuse may legitimately
skip part of; it is reported beside ``dense`` and is never part of ``mfu``.
A multiply-add counts as two operations.
"""


def latent_tokens(m):
    t = m["frames"] // m["t_vae_factor"] // m["t_patch"]
    s = m["img_res"] // m["vae_factor"] // m["patch"]
    return t * s * s


def step_flops(m):
    d, L = m["d_model"], m["txt_tokens"]
    F = int(d * m["mlp_ratio"])
    n = latent_tokens(m)
    N = n + L
    pin = m["t_patch"] * m["patch"] ** 2 * m["in_channels"]
    embed = 2 * n * pin * d + 2 * L * m["txt_dim"] * d \
        + 2 * 256 * d + 2 * d * d
    block = 2 * d * 6 * d + 4 * 2 * N * d * d + 3 * 2 * N * d * F
    head = 2 * d * 2 * d + 2 * n * d * pin
    return {"dense": embed + m["num_layers"] * block + head,
            "attention": m["num_layers"] * 4 * N * N * d}
