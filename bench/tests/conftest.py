"""Helpers for the benchmark's own tests: the configurations at a size a
CPU test run holds.  Run with ``python -m pytest bench/tests``."""

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

# Nothing these tests compile goes to the checkout's compile cache.
jax.config.update("jax_enable_compilation_cache", False)

# Every width cut, every kind of layer kept, so the CPU runs in seconds.
SMALL = {
    "vdit-paper": dict(frames=8, img_res=64, num_layers=2, d_model=64,
                       num_heads=2, in_channels=4, txt_tokens=8,
                       txt_dim=32, axes_dim=[8, 12, 12]),
    "flux-dev": dict(img_res=64, latent_res=8, n_double_blocks=1,
                     n_single_blocks=2, d_model=64, num_heads=2,
                     in_channels=4, txt_tokens=8, txt_dim=32,
                     axes_dim=[8, 12, 12]),
}
CELLS = {"vdit-paper": "vdit-gen512-ripple", "flux-dev": "flux-1024-ripple"}


@pytest.fixture
def small_cell(monkeypatch):
    """``(bench, cell, config, traffic)`` of a configuration's cell at the
    small size, with the program's shape of that name resized to match."""
    from bench import harness
    import repro.configs as configs

    def make(config_name, **model):
        bench, cell, config, traffic = harness.load_cell(CELLS[config_name])
        config["model"].update(SMALL[config_name], **model)
        config["published"] = dict(config["model"])
        config["reduced"] = []
        res = config["model"]["img_res"]
        orig = configs.get_config

        def resized(name):
            a = orig(name)
            return dataclasses.replace(a, shapes=tuple(
                dataclasses.replace(s, img_res=res) for s in a.shapes))

        monkeypatch.setattr(configs, "get_config", resized)
        return bench, cell, config, traffic

    return make
