"""The serving path's profiler spans, named scopes and reuse counters
(DESIGN.md §15.5): a traced two-layer streaming engine run of vdit and
of mmdit, the counters against the model's own stats and the kernel's
block flags, the scopes against the arithmetic, and the counters under
a sharded dispatch plan."""

import contextlib
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.distributed.sharding import NULL_CTX
from repro.launch.serve import build_sampler, make_sampler_factory
from repro.launch.workloads import _denoise_call, latent_shape_for, model_fns
from repro.models.params import init_params
from repro.serving.engine import COUNTERS, DiffusionEngine, GenRequest

STEPS = 4
PER_STEP = ("engine.dispatch", "engine.wait", "engine.copy_out",
            "engine.publish")


def _small(family):
    """A two-layer model of the family, snapping at steps 1 and 2."""
    if family == "vdit":
        arch = get_smoke_config("vdit-paper")
        model = dataclasses.replace(arch.model, num_layers=2)
        shape = arch.shape("gen_512")
    else:
        arch = get_smoke_config("flux-dev")
        model = dataclasses.replace(arch.model, n_double_blocks=1,
                                    n_single_blocks=1)
        shape = arch.shape("gen_1024")
    arch = dataclasses.replace(arch, model=model, ripple=dataclasses.replace(
        arch.ripple, i_min=1, i_max=2))
    return arch, dataclasses.replace(shape, steps=STEPS)


def _request(arch, shape, rid, stream_every=1):
    m = arch.model
    txt = np.random.default_rng(rid).standard_normal(
        (m.txt_tokens, m.txt_dim)).astype(np.float32)
    return GenRequest(request_id=rid, txt=txt, steps=STEPS, seed=rid,
                      latent_shape=tuple(latent_shape_for(arch, shape)),
                      stream_every=stream_every)


def _engine_spans(trace_dir):
    """``[(name, start, end, args)]`` of every engine span, by start."""
    import glob

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    start = ev.start_ns * 1e-9
                    out.append((ev.name, start,
                                start + ev.duration_ns * 1e-9,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module", params=["vdit", "mmdit"])
def traced(request, tmp_path_factory):
    """Two streamed requests served one after the other under a profiler
    trace (after a warm request outside it): the engine spans, the second
    request's streamed latents, and what produced them.  The first
    batch's ``engine.take_batch`` began before the trace, so only the
    second batch has every span."""
    arch, shape = _small(request.param)
    params = init_params(model_fns(arch), jax.random.PRNGKey(0))
    factory, plan_fn = make_sampler_factory(arch, (shape,), params,
                                            sentinel=True)
    eng = DiffusionEngine(sampler_factory=factory, max_batch=1,
                          plan_fn=plan_fn, guardrail=True)
    eng.start()
    try:
        eng.submit(_request(arch, shape, 0))
        eng.result(0, timeout=600)
        trace_dir = str(tmp_path_factory.mktemp("trace"))
        req = _request(arch, shape, 2)
        jax.profiler.start_trace(trace_dir)
        try:
            eng.submit(_request(arch, shape, 1))
            eng.submit(req)
            chunks = list(eng.stream(2, timeout=600))
            res = eng.result(2, timeout=600)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    return dict(arch=arch, shape=shape, params=params, req=req,
                chunks=chunks, result=res, spans=_engine_spans(trace_dir))


def test_engine_spans_nested_and_ordered(traced):
    """One batch: take_batch, assemble, then per step dispatch, wait,
    copy_out and publish, then finish — one after another on the engine
    thread, each with the batch index and each per-step span with its
    step."""
    spans = traced["spans"]
    take = [s for s in spans if s[0] == "engine.take_batch"]
    assert len(take) == 1
    batch = take[0][3]["batch"]
    assert take[0][3]["size"] == 1 and take[0][3]["queue_wait_ms"] >= 0
    assert {s[3]["batch"] for s in spans} == {batch - 1, batch}
    mine = [s for s in spans if s[3]["batch"] == batch]
    want = ["engine.take_batch", "engine.assemble"] \
        + [n for _ in range(STEPS) for n in PER_STEP] + ["engine.finish"]
    assert [s[0] for s in mine] == want
    steps = [s[3]["step"] for s in mine if s[0] in PER_STEP]
    assert steps == [s for s in range(STEPS) for _ in PER_STEP]
    for a, b in zip(mine, mine[1:]):
        assert a[2] <= b[1], (a[0], b[0])  # each ends before the next


def test_publish_counters_match_model_stats(traced):
    """The counters on each step's ``engine.publish`` are the model's own
    RippleStats for that step's operands, and ``GenResult.reuse`` is
    their mean."""
    from repro.diffusion.schedule import DDPMSchedule

    arch, params, req = traced["arch"], traced["params"], traced["req"]
    fam = arch.family
    x = jax.random.normal(jax.random.PRNGKey(req.seed),
                          req.latent_shape)[None]
    txt = jnp.asarray(req.txt)[None]
    cond = {"txt": txt}
    if fam == "mmdit":
        cond["vec"] = jnp.zeros((1, 768))
        ts = jnp.linspace(1.0, 0.0, STEPS + 1)
    else:
        ts = jnp.linspace(DDPMSchedule().num_train_steps - 1, 0,
                          STEPS).astype(jnp.int32)

    @jax.jit
    def stats(x, t, step):
        _, st = _denoise_call(arch, params, x, jnp.full((1,), t), cond,
                              step, STEPS, NULL_CTX, use_ripple=True,
                              with_stats=True)
        return jnp.stack([st.q_snap_frac, st.k_snap_frac, st.savings,
                          st.structural_savings])

    batch = max(s[3]["batch"] for s in traced["spans"])
    publish = [s[3] for s in traced["spans"]
               if s[0] == "engine.publish" and s[3]["batch"] == batch]
    assert len(publish) == STEPS
    inputs = [x] + [jnp.asarray(c)[None] for c in traced["chunks"][:-1]]
    for step, (args, xin) in enumerate(zip(publish, inputs)):
        want = np.asarray(stats(xin, ts[step], jnp.asarray(step)))
        got = np.asarray([args[n] for n in COUNTERS])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert publish[1]["paper_savings"] > 0  # step 1 snaps
    mean = np.mean([[a[n] for n in COUNTERS] for a in publish], axis=0)
    np.testing.assert_allclose([traced["result"].reuse[n] for n in COUNTERS],
                               mean, rtol=1e-6)


def test_skipped_is_the_kernels_block_flags():
    """On the ripple kernel, ``skipped`` is ``ripple_block_stats`` of the
    block flags of the snapped operands the kernel ran on."""
    from repro.config.base import RippleConfig
    from repro.core.dispatch import attention_dispatch, resolve_plan
    from repro.core.policy import get_policy
    from repro.kernels.ripple.ops import (ripple_block_flags,
                                          ripple_block_stats)

    grid, d = (4, 8, 16), 32
    n = grid[0] * grid[1] * grid[2]
    base = jax.random.normal(jax.random.PRNGKey(0), (1, 2, n // 2, d))
    # Pairs of nearly equal tokens, so whole blocks snap to collapse.
    pairs = jnp.repeat(base, 2, axis=2)
    noise = jax.random.normal(jax.random.PRNGKey(1), pairs.shape)
    q = pairs + 0.01 * noise
    k = pairs + 0.01 * jnp.roll(noise, 1, axis=-1)
    v = jax.random.normal(jax.random.PRNGKey(2), pairs.shape)
    cfg = RippleConfig(enabled=True, granularity="token",
                       fixed_threshold=0.5, i_min=0, i_max=1,
                       backend="pallas", axes=("x",))
    step = jnp.asarray(0)
    _, st = attention_dispatch(q, k, v, grid=grid, cfg=cfg, step=step,
                               total_steps=10, with_stats=True)
    plan = resolve_plan(q.shape, v.shape, cfg, grid=grid)
    pol = get_policy("ripple")
    dec = pol.decide(q, k, grid=grid, cfg=cfg,
                     thetas=pol.thetas_for(cfg, step, 10))
    want = ripple_block_stats(*ripple_block_flags(
        dec.q, dec.k, block_q=plan.block_q, block_k=plan.block_k))
    assert plan.backend == "pallas"
    assert float(want) > 0.1
    assert float(st.structural_savings) == pytest.approx(float(want))
    ref = pol.stats(dec)
    assert float(st.savings) == pytest.approx(float(ref.savings))
    assert float(st.q_snap_frac) == pytest.approx(float(ref.q_snap_frac))


@pytest.mark.parametrize("family", ["vdit", "mmdit"])
def test_scopes_leave_latents_bitwise(family, monkeypatch):
    """Named scopes are trace-time metadata: the served latents are
    bitwise the same with every scope removed."""
    arch, shape = _small(family)
    params = init_params(model_fns(arch), jax.random.PRNGKey(0))
    req = _request(arch, shape, 3)
    noise = jax.random.normal(jax.random.PRNGKey(req.seed),
                              req.latent_shape)[None]
    txt = jnp.asarray(req.txt)[None]
    rngs = jax.random.PRNGKey(req.seed)[None]

    def served():
        fn, _ = build_sampler(arch, shape, params, sentinel=True)
        return np.asarray(fn(noise, txt, rngs)[0])

    scoped = served()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    np.testing.assert_array_equal(served(), scoped)


def test_sharded_counters_match_one_device(tmp_path):
    """Under ``--mesh 1x4`` on four virtual devices the attention plans
    stay head-sharded with the counters on, and a served request reports
    the counters (and latents) of one device."""
    code = textwrap.dedent("""
        import numpy as np
        from repro.core import dispatch
        from repro.launch import serve

        argv = ["--smoke", "--shape", "gen_512", "--steps", "4",
                "--requests", "1", "--max-batch", "1",
                "model.num_layers=1", "model.d_model=256",
                "model.num_heads=4",
                "ripple.i_min=1", "ripple.i_max=2"]
        one = serve.main(argv)
        dispatch.clear_plan_cache()
        four = serve.main(argv + ["--mesh", "1x4"])
        plans = [p for p in dispatch._PLAN_CACHE.values()
                 if p.policy == "ripple"]
        assert plans and all(p.sharded and p.head_shards == 4
                             for p in plans), plans
        for name in ("q_snap", "k_snap", "paper_savings", "skipped"):
            a, b = one["reuse"]["0"][name], four["reuse"]["0"][name]
            assert abs(a - b) <= 1e-6 * max(abs(a), 1e-6), (name, a, b)
        assert one["reuse"]["0"]["q_snap"] > 0
        np.testing.assert_array_equal(one["latents"][0],
                                      four["latents"][0])
        print("sharded counters OK")
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH="src",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "sharded counters OK" in r.stdout
