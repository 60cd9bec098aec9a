"""The FLOP counts against XLA's own count of one jitted layer.

At a small size on the CPU, the program's model with one layer (one
double and one single block for flux-dev) and reuse off is compiled, and
``cost_analysis()["flops"]`` is read.  XLA counts every operation: the
matrix products as two per multiply-add, as the counts here do, and also
the elementwise work (norms, RoPE, softmax, activations), which the
counts leave out.  So XLA's number must lie at or above the count of
products and within ``ELEMENTWISE`` (8%) of it: elementwise work is tens
of operations per token and channel against 2d per token and channel for
each product, which at 272 tokens of width 256 read 3.2% above the count
for vdit-paper and 6.5% for flux-dev (tanh-GELU); a product counted
twice or left out moves the ratio by more than that.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench import harness

ELEMENTWISE = 0.08
SIZES = {
    "vdit-paper": dict(frames=8, img_res=256, num_layers=1, d_model=256,
                       num_heads=4, in_channels=4, txt_tokens=16,
                       txt_dim=128, axes_dim=[16, 24, 24]),
    "flux-dev": dict(img_res=256, latent_res=32, n_double_blocks=1,
                     n_single_blocks=1, d_model=256, num_heads=4,
                     in_channels=4, txt_tokens=16, txt_dim=128,
                     axes_dim=[16, 24, 24]),
}


def _xla_flops(arch, config):
    from repro.launch.workloads import model_fns
    from repro.models import mmdit, vdit
    from repro.models.params import init_params

    ref = harness.config_module(config, "reference")
    m = arch.model
    params = jax.eval_shape(
        lambda: init_params(model_fns(arch), jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1,) + ref.latent_shape(config["model"]),
                             jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.float32)
    txt = jax.ShapeDtypeStruct((1, m.txt_tokens, m.txt_dim), jnp.float32)
    off = dataclasses.replace(arch.ripple, enabled=False)
    if arch.family == "vdit":
        fn = lambda p, x, t, c: vdit.vdit_apply(p, x, t, c, m, ripple=off)
        args = (params, x, t, txt)
    else:
        vec = jax.ShapeDtypeStruct((1, 768), jnp.float32)
        fn = lambda p, x, t, c, v: mmdit.mmdit_apply(p, x, t, c, v, m,
                                                     ripple=off)
        args = (params, x, t, txt, vec)
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"]


@pytest.mark.parametrize("name", ["vdit-paper", "flux-dev"])
def test_flops_match_xla(small_cell, name):
    bench, cell, config, traffic = small_cell(name, **SIZES[name])
    arch, _ = harness.program_arch(config, traffic)
    counted = harness.config_module(config, "flops").step_flops(
        config["model"])
    products = counted["dense"] + counted["attention"]
    xla = _xla_flops(arch, config)
    assert products <= xla <= products * (1 + ELEMENTWISE), \
        (products, xla, xla / products)
