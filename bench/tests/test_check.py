"""The comparison that decides ``correct``: the served steps agree with
the reference, its float8 control does not, and a run whose timed path
is broken comes out not correct, once for each fault a cell can have.

All at the small size of ``conftest.SMALL`` on the CPU; the limits are
the configurations' own, set from runs on the chip at full size.
"""

import dataclasses

import jax.numpy as jnp
import pytest

from bench import harness

SEED = 2 ** 31 + 12345  # more than 31 bits, as the command accepts


@pytest.mark.parametrize("name", ["vdit-paper", "flux-dev"])
def test_sound_run_passes_and_control_fails(small_cell, name):
    """A whole run: the served steps are within the limit, the float8
    control on the same steps is not, and the result line is whole."""
    bench, cell, config, traffic = small_cell(name)
    out = harness.run_cell(bench, cell, config, traffic, seed=SEED,
                           seconds=2.0, trace=False, require_chip=False,
                           control=True)
    limit = config["limits"]["rel_err"]
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["rel_err"]["value"] < limit \
        < out["control"]["control_rel_err"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}


def _broken(kind, monkeypatch):
    """A fault for ``run_cell`` that makes the timed path commit ``kind``
    of error."""
    if kind == "snap_2theta":
        # The Δ-check snaps at twice the thresholds Eq. 4 allows.
        def double(arch):
            r = arch.ripple
            return dataclasses.replace(arch, ripple=dataclasses.replace(
                r, theta_min=2 * r.theta_min, theta_max=2 * r.theta_max))

        return {"arch": double}
    if kind == "attention_mean":
        # Every reuse-path attention call returns the mean of the values,
        # as uniform attention would.
        from repro.core import dispatch

        def mean_of_values(d, v, scale, *, plan, cfg):
            return jnp.broadcast_to(jnp.mean(v, axis=-2, keepdims=True),
                                    v.shape)

        monkeypatch.setattr(dispatch, "_execute_backend", mean_of_values)
        return {}

    def wrap(factory):
        def broken_factory(latent_shape, steps, policy=None,
                           reuse_every=None, stream_every=None):
            fn = factory(latent_shape, steps, policy, reuse_every,
                         stream_every)

            def sample_fn(noise, txt, rngs, resume=None):
                half = noise.shape[0] // 2
                for x, aux in fn(noise, txt, rngs, resume=resume):
                    if kind == "state_unchanged":
                        out = noise  # no step ever moves the latents
                    elif kind == "half_batch":
                        # The second half of the batch is never stepped.
                        out = jnp.concatenate([x[:half], noise[half:]])
                    else:  # answer altered where it is produced
                        out = x.at[..., 0].set(x[..., 1])
                    yield out, aux

            return sample_fn

        return broken_factory

    return {"factory": wrap}


@pytest.mark.parametrize("name,kind", [
    ("vdit-paper", "state_unchanged"),
    ("vdit-paper", "answer_altered"),
    ("vdit-paper", "snap_2theta"),
    ("vdit-paper", "attention_mean"),
    ("flux-dev", "state_unchanged"),
    ("flux-dev", "half_batch"),
    ("flux-dev", "answer_altered"),
    ("flux-dev", "snap_2theta"),
    ("flux-dev", "attention_mean"),
])
def test_broken_timed_path_is_not_correct(small_cell, monkeypatch, name,
                                          kind):
    """With the cell's own sample size: a fault that only some slots or
    steps show must still be drawn."""
    bench, cell, config, traffic = small_cell(name)
    out = harness.run_cell(bench, cell, config, traffic, seed=SEED,
                           seconds=2.0, trace=False, require_chip=False,
                           fault=_broken(kind, monkeypatch))
    assert out["correct"] is False
    assert out["checks"]["rel_err"]["value"] > \
        out["checks"]["rel_err"]["limit"]


def test_server_that_cannot_serve_ends_setup(small_cell):
    """A step program that fails before any step lands is reported at
    once, not after set-up's 1200-s allowance."""
    bench, cell, config, traffic = small_cell("vdit-paper")

    def failing(factory):
        def broken_factory(latent_shape, steps, policy=None,
                           reuse_every=None, stream_every=None):
            raise RuntimeError("cannot build the step program")

        return broken_factory

    with pytest.raises(harness.BenchError, match="before any step landed"):
        harness.run_cell(bench, cell, config, traffic, seed=SEED,
                         seconds=2.0, trace=False, require_chip=False,
                         fault={"factory": failing})
