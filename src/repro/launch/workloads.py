"""Workload construction: (arch x shape x mesh) -> a jittable step
function + abstract inputs + shardings.

This is the single bridge the dry-run, the trainer, and the server all
go through, so the thing that compiles in the dry-run is exactly the
thing that runs.  ``build_workload`` returns a :class:`Workload` whose
``lower()`` produces the pjit-lowered artifact for roofline analysis.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config.base import ArchConfig, ShapeSpec, TrainConfig
from repro.core import dispatch as dispatch_lib
from repro.diffusion.schedule import DDPMSchedule, RectifiedFlowSchedule
from repro.distributed import sharding as shlib
from repro.distributed.sharding import ShardCtx
from repro.models import (dit as dit_lib, efficientnet as eff_lib,
                          mmdit as mmdit_lib, transformer_lm as lm_lib,
                          unet as unet_lib, vdit as vdit_lib, vit as vit_lib)
from repro.models.params import abstract_params, init_params, logical_axes
from repro.training import train_loop
from repro.training.train_loop import TrainState


@dataclasses.dataclass
class Workload:
    arch: ArchConfig
    shape: ShapeSpec
    mesh: Optional[Mesh]
    fn: Callable                      # jit-able step function
    args: Tuple[Any, ...]             # abstract (or concrete) args
    in_shardings: Any
    out_shardings: Any
    donate_argnums: Tuple[int, ...] = ()
    # multiplier to turn one lowered step into the full workload
    # (e.g. sampler steps for 'generate' shapes)
    steps_multiplier: int = 1
    # cost-probe metadata (see dryrun.run_cell): trip count of the
    # primary scan-over-layers loop, and how to probe the exact cost.
    loop_trips: int = 0
    probe: str = "two_point"  # 'two_point' | 'unroll' | 'none'
    # resolved attention-dispatch plan for the cell's self-attention
    # shape (diffusion generate cells; None elsewhere) — what the
    # dry-run and the server report as the execution strategy.
    attn_plan: Optional[dispatch_lib.DispatchPlan] = None

    def jitted(self):
        return jax.jit(self.fn, in_shardings=self.in_shardings,
                       out_shardings=self.out_shardings,
                       donate_argnums=self.donate_argnums)

    def lower(self):
        return self.jitted().lower(*self.args)


# --- family dispatch tables --------------------------------------------------


def model_fns(arch: ArchConfig):
    fam = arch.family
    if fam == "lm":
        return lm_lib.lm_defs(arch.model)
    if fam == "dit":
        return dit_lib.dit_defs(arch.model)
    if fam == "mmdit":
        return mmdit_lib.mmdit_defs(arch.model)
    if fam == "unet":
        return unet_lib.unet_defs(arch.model)
    if fam == "vit":
        return vit_lib.vit_defs(arch.model)
    if fam == "effnet":
        return eff_lib.effnet_defs(arch.model)
    if fam == "vdit":
        return vdit_lib.vdit_defs(arch.model)
    raise ValueError(fam)


def _named(mesh, spec):
    return NamedSharding(mesh, spec) if mesh is not None else None


def _leaf_is_axes(x):
    return isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)


def _state_shardings(arch, defs, mesh, train_cfg: TrainConfig):
    axes = logical_axes(defs)
    state_axes = train_loop.train_state_logical_axes(axes, train_cfg)
    abstract = train_loop.abstract_train_state(abstract_params(defs), train_cfg)
    if mesh is None:
        return abstract, None
    rules = shlib.param_rules(mesh)
    shardings = jax.tree_util.tree_map(
        lambda ax, ab: NamedSharding(
            mesh, shlib.spec_from_axes(ax, rules, ab.shape, mesh)),
        state_axes, abstract, is_leaf=_leaf_is_axes)
    return abstract, shardings


def _param_shardings(defs, mesh, fsdp: bool = True, dtype=None):
    axes = logical_axes(defs)
    abstract = abstract_params(defs)
    if dtype is not None:
        # serving-precision weights (bf16 checkpoints at decode time)
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, abstract)
    if mesh is None:
        return abstract, None
    rules = shlib.param_rules(mesh, fsdp=fsdp)
    shardings = jax.tree_util.tree_map(
        lambda ax, ab: NamedSharding(
            mesh, shlib.spec_from_axes(ax, rules, ab.shape, mesh)),
        axes, abstract, is_leaf=_leaf_is_axes)
    return abstract, shardings


def _effective_accum(accum: int, global_batch: int, mesh) -> int:
    """Clamp grad accumulation so each microbatch still divides the batch
    shards: on the 2x16x16 mesh the batch axis is 32-way, so accum must
    leave microbatches of >= 32 samples. Largest accum' <= accum with
    (B/accum') % shards == 0."""
    if mesh is None:
        return accum
    shards = shlib.axis_size(mesh, shlib.batch_axes(mesh)) or 1
    a = min(accum, max(global_batch // shards, 1))
    while a > 1 and (global_batch % a or (global_batch // a) % shards):
        a -= 1
    return max(a, 1)


def _batch_sharding(mesh, batch_dims: int, extra=(), size0: int = 0):
    """Shard dim0 over the largest prefix of (pod, data) dividing it;
    remaining dims replicated/extra."""
    bd = list(shlib.batch_axes(mesh))
    if mesh is not None and size0:
        while bd and size0 % shlib.axis_size(mesh, tuple(bd)) != 0:
            bd.pop()
    bd = tuple(bd)
    return _named(mesh, P(bd if bd else None, *extra,
                          *([None] * (batch_dims - 1 - len(extra)))))


# --- LM workloads -------------------------------------------------------------


def _lm_train(arch: ArchConfig, shape: ShapeSpec, mesh) -> Workload:
    cfg = arch.model
    tc = dataclasses.replace(
        arch.train, grad_accum=_effective_accum(
            arch.train.grad_accum, shape.global_batch, mesh))
    defs = lm_lib.lm_defs(cfg)
    ctx = ShardCtx(mesh, shlib.train_act_rules(mesh, tc.seq_parallel))

    def loss_fn(params, batch, rng):
        return lm_lib.lm_loss(params, batch["tokens"], batch["targets"], cfg,
                              ctx=ctx, remat=tc.remat,
                              remat_policy=tc.remat_policy)

    step = train_loop.make_train_step(loss_fn, tc)
    abstract_state, state_sh = _state_shardings(arch, defs, mesh, tc)
    B, S = shape.global_batch, shape.seq_len
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    bsh = {k: _batch_sharding(mesh, 2, size0=B) for k in batch}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return Workload(
        arch=arch, shape=shape, mesh=mesh, fn=step,
        args=(abstract_state, batch, rng),
        in_shardings=(state_sh, bsh, _named(mesh, P())),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
        loop_trips=cfg.num_layers)


def _lm_prefill(arch: ArchConfig, shape: ShapeSpec, mesh) -> Workload:
    cfg = arch.model
    defs = lm_lib.lm_defs(cfg)
    ctx = ShardCtx(mesh, shlib.decode_act_rules(mesh))
    max_len = shape.seq_len

    def fn(params, tokens):
        return lm_lib.lm_prefill(params, tokens, cfg, max_len, ctx=ctx)

    ap, psh = _param_shardings(defs, mesh)
    B, S = shape.global_batch, shape.seq_len
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    cache_ax = lm_lib.cache_logical_axes()
    rules = shlib.decode_act_rules(mesh)
    cache_abs = lm_lib.abstract_cache(cfg, B, max_len)
    cache_sh = tuple(
        _named(mesh, shlib.spec_from_axes(ax, rules, ab.shape, mesh))
        for ax, ab in zip(cache_ax, cache_abs))
    return Workload(
        arch=arch, shape=shape, mesh=mesh, fn=fn,
        args=(ap, tokens),
        in_shardings=(psh, _batch_sharding(mesh, 2, size0=B)),
        out_shardings=(None, cache_sh),
        loop_trips=cfg.num_layers)


def _lm_decode(arch: ArchConfig, shape: ShapeSpec, mesh) -> Workload:
    cfg = arch.model
    defs = lm_lib.lm_defs(cfg)
    long_ctx = shape.seq_len >= 262144
    rules = shlib.decode_act_rules(
        mesh, long_context=long_ctx,
        replicate_heads=arch.decode_replicate_heads)
    ctx = ShardCtx(mesh, rules)
    B, S = shape.global_batch, shape.seq_len

    def fn(params, token, cache, index):
        return lm_lib.lm_decode_step(params, token, cache, index, cfg,
                                     ctx=ctx)

    # NOTE(§Perf): dtype=jnp.bfloat16 here (serving-precision weights)
    # should halve the no-FSDP weight footprint, but the compiled module
    # reports *higher* temp bytes (23.9 vs 18.7 GB) — XLA materializes
    # f32 upcasts of the bf16 weights for the f32 logit path instead of
    # fusing them.  Kept at checkpoint precision pending a kernel-level
    # fix; see EXPERIMENTS.md §Perf cell 3 iteration 3.
    ap, psh = _param_shardings(defs, mesh, fsdp=not arch.decode_no_fsdp)
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    cache_abs = lm_lib.abstract_cache(cfg, B, S)
    cache_ax = lm_lib.cache_logical_axes()
    cache_sh = tuple(
        _named(mesh, shlib.spec_from_axes(ax, rules, ab.shape, mesh))
        for ax, ab in zip(cache_ax, cache_abs))
    index = jax.ShapeDtypeStruct((), jnp.int32)
    return Workload(
        arch=arch, shape=shape, mesh=mesh, fn=fn,
        args=(ap, token, cache_abs, index),
        in_shardings=(psh, _batch_sharding(mesh, 2, size0=B), cache_sh,
                      _named(mesh, P())),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
        loop_trips=cfg.num_layers)


# --- diffusion workloads -------------------------------------------------------


def _diffusion_batch_specs(arch: ArchConfig, shape: ShapeSpec, mesh,
                           train: bool):
    """Abstract latents/conditioning for one diffusion workload cell."""
    fam = arch.family
    m = arch.model
    res = shape.img_res
    B = shape.batch
    if fam == "dit":
        lat = (B, res // m.vae_factor, res // m.vae_factor, m.in_channels)
        cond = {"labels": jax.ShapeDtypeStruct((B,), jnp.int32)}
    elif fam == "mmdit":
        lr = res // 8
        lat = (B, lr, lr, m.in_channels)
        cond = {"txt": jax.ShapeDtypeStruct((B, m.txt_tokens, m.txt_dim),
                                            jnp.float32),
                "vec": jax.ShapeDtypeStruct((B, 768), jnp.float32)}
    elif fam == "unet":
        lr = res // 8
        lat = (B, lr, lr, m.in_channels)
        cond = {"ctx": jax.ShapeDtypeStruct((B, m.ctx_tokens, m.ctx_dim),
                                            jnp.float32)}
    elif fam == "vdit":
        g = m.grid(img_res=res)
        lat = (B, g[0] * m.t_patch, g[1] * m.patch, g[2] * m.patch,
               m.in_channels)
        cond = {"txt": jax.ShapeDtypeStruct((B, m.txt_tokens, m.txt_dim),
                                            jnp.float32)}
    else:
        raise ValueError(fam)
    return jax.ShapeDtypeStruct(lat, jnp.float32), cond


def _denoise_call(arch: ArchConfig, params, x, t, cond, step, total, ctx,
                  use_ripple: bool, dstate=None, with_stats=False):
    """One denoiser forward.  ``dstate`` threads the per-layer decision
    cache (DESIGN.md §13) — vdit only; the call then returns
    ``(out, new_dstate)``.  ``with_stats`` (vdit only) appends the
    layer-mean :class:`~repro.core.policy.RippleStats`."""
    fam = arch.family
    m = arch.model
    rip = arch.ripple if use_ripple else dataclasses.replace(
        arch.ripple, enabled=False)
    kw = dict(ripple=rip, step=step, total_steps=total, ctx=ctx)
    if (dstate is not None or with_stats) and fam != "vdit":
        raise ValueError(f"decision-cache state and reuse stats are only "
                         f"threaded through the vdit family, not {fam!r}")
    if fam == "dit":
        out = dit_lib.dit_apply(params, x, t, cond["labels"], m, **kw)
        return out[..., : m.in_channels]  # drop sigma for the ODE path
    if fam == "mmdit":
        return mmdit_lib.mmdit_apply(params, x, t, cond["txt"], cond["vec"],
                                     m, **kw)
    if fam == "unet":
        return unet_lib.unet_apply(params, x, t, cond["ctx"], m, **kw)
    if fam == "vdit":
        return vdit_lib.vdit_apply(params, x, t, cond["txt"], m,
                                   decision_state=dstate,
                                   with_stats=with_stats, **kw)
    raise ValueError(fam)


def _attn_seq_fallback(arch, mesh, rules):
    """Archs whose head count doesn't divide the model axis (flux: 24
    heads on 16) shard attention over the query-sequence dim instead
    (context parallelism): logits (B, H, Nq/16, Nk), K/V gathered."""
    heads = getattr(arch.model, "num_heads", 0)
    if mesh is not None and "model" in mesh.axis_names and heads and             heads % mesh.shape["model"] != 0:
        rules = dict(rules)
        rules["attn_seq"] = "model"
    return rules


def _diffusion_train(arch: ArchConfig, shape: ShapeSpec, mesh) -> Workload:
    tc = dataclasses.replace(
        arch.train, grad_accum=_effective_accum(
            arch.train.grad_accum, shape.batch, mesh))
    defs = model_fns(arch)
    ctx = ShardCtx(mesh, _attn_seq_fallback(
        arch, mesh, shlib.train_act_rules(mesh)))
    ddpm = DDPMSchedule()
    rf = RectifiedFlowSchedule()
    fam = arch.family
    m = arch.model

    def loss_fn(params, batch, rng):
        x0 = batch["latents"]
        B = x0.shape[0]
        k1, k2 = jax.random.split(rng)
        noise = jax.random.normal(k1, x0.shape, x0.dtype)
        if fam == "mmdit":  # rectified flow
            t = rf.sample_t(k2, B)
            xt = rf.interpolate(x0, noise, t)
            target = rf.velocity_target(x0, noise)
            pred = _denoise_call(arch, params, xt, t, batch, None, None, ctx,
                                 use_ripple=False)
        else:
            t = jax.random.randint(k2, (B,), 0, ddpm.num_train_steps)
            xt = ddpm.add_noise(x0, noise, t)
            target = noise
            pred = _denoise_call(arch, params, xt, t.astype(jnp.float32),
                                 batch, None, None, ctx, use_ripple=False)
        loss = jnp.mean(jnp.square(pred.astype(jnp.float32)
                                   - target.astype(jnp.float32)))
        return loss, {"mse": loss}

    step = train_loop.make_train_step(loss_fn, tc)
    abstract_state, state_sh = _state_shardings(arch, defs, mesh, tc)
    lat, cond = _diffusion_batch_specs(arch, shape, mesh, train=True)
    batch = {"latents": lat, **cond}
    bsh = {k: _batch_sharding(mesh, v.ndim, size0=v.shape[0])
           for k, v in batch.items()}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    trips, probe = _diffusion_probe_info(arch)
    return Workload(
        arch=arch, shape=shape, mesh=mesh, fn=step,
        args=(abstract_state, batch, rng),
        in_shardings=(state_sh, bsh, _named(mesh, P())),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
        loop_trips=trips, probe=probe)


def _diffusion_probe_info(arch: ArchConfig):
    fam = arch.family
    if fam in ("dit", "vdit"):
        return arch.model.num_layers, "two_point"
    if fam == "mmdit":
        # two scans with different trip counts (double/single blocks):
        # the two-point identity can't separate them -> full unroll.
        return 0, "unroll"
    return 0, "none"  # unet: python-level loops, HLO already explicit


def _diffusion_generate(arch: ArchConfig, shape: ShapeSpec, mesh) -> Workload:
    """One denoising step exactly as the sampler invokes it (with CFG
    batch doubling for the CFG families); steps_multiplier carries the
    sampler length for the roofline report."""
    defs = model_fns(arch)
    rules = shlib.seqpar_act_rules(mesh, shape.batch * _cfg_factor(arch)) \
        if mesh is not None else None
    if rules is not None:
        rules = _attn_seq_fallback(arch, mesh, rules)
    ctx = ShardCtx(mesh, rules)
    fam = arch.family
    total = shape.steps

    def fn(params, x, t, cond, step):
        return _denoise_call(arch, params, x, t, cond, step, total, ctx,
                             use_ripple=True)

    ap, psh = _param_shardings(defs, mesh)
    lat, cond = _diffusion_batch_specs(arch, shape, mesh, train=False)
    f = _cfg_factor(arch)
    lat = jax.ShapeDtypeStruct((lat.shape[0] * f, *lat.shape[1:]), lat.dtype)
    cond = {k: jax.ShapeDtypeStruct((v.shape[0] * f, *v.shape[1:]), v.dtype)
            for k, v in cond.items()}
    t = jax.ShapeDtypeStruct((lat.shape[0],), jnp.float32)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    bsh_lat = _named(mesh, _gen_spec(mesh, lat.shape, rules))
    bsh_cond = {k: _named(mesh, _gen_spec(mesh, v.shape, rules))
                for k, v in cond.items()}
    return Workload(
        arch=arch, shape=shape, mesh=mesh, fn=fn,
        args=(ap, lat, t, cond, step),
        in_shardings=(psh, bsh_lat, _named(mesh, P()), bsh_cond,
                      _named(mesh, P())),
        out_shardings=bsh_lat,
        steps_multiplier=shape.steps,
        loop_trips=_diffusion_probe_info(arch)[0],
        probe=_diffusion_probe_info(arch)[1],
        attn_plan=attention_plan(arch, shape, mesh=mesh))


def attention_plan(arch: ArchConfig, shape: ShapeSpec,
                   mesh: Optional[Mesh] = None,
                   policy: Optional[str] = None):
    """Resolved dispatch plan for the cell's joint self-attention shape.

    Metadata only (the models resolve their own plans at trace time via
    ``attention_dispatch``); UNet is skipped — its attention runs at
    several resolutions with level-dependent head dims.  ``mesh`` makes
    the recorded batch/head sharding match what the sharded serving path
    will execute (DESIGN.md §10); ``policy`` overrides the arch config's
    reuse policy (DESIGN.md §11).
    """
    m = arch.model
    fam = arch.family
    res = shape.img_res
    if fam == "dit":
        n = m.num_tokens(res)
    elif fam == "mmdit":
        n = (res // 8 // m.patch) ** 2 + m.txt_tokens
    elif fam == "vdit":
        g = m.grid(img_res=res)
        n = g[0] * g[1] * g[2] + m.txt_tokens
    else:
        return None
    heads = m.num_heads
    bh = max(shape.batch, 1) * _cfg_factor(arch) * heads
    return dispatch_lib.plan_for_shape(n, m.d_model // heads, arch.ripple,
                                       batch_heads=bh, heads=heads,
                                       mesh=mesh, policy=policy)


def vdit_decision_state(arch: ArchConfig, img_res: int, batch: int,
                        policy: Optional[str] = None,
                        compute_dtype=jnp.bfloat16):
    """Per-layer decision-cache state for one vdit sampler invocation
    (DESIGN.md §13): an all-zeros stacked CachedDecision matching the
    model's per-layer self-attention operands at this resolution and
    batch.  Safe to call inside the jitted sampler (the zeros become
    constants); step 0 always refreshes, so the zeros are never applied.
    Returns None when the config can't cache (inactive ripple, or a
    policy without the capability) — callers fall back to the plain
    per-step path."""
    from repro.core import decision_cache

    pol = policy or arch.ripple.policy
    if not decision_cache.supports_cache(arch.ripple, pol):
        return None
    m = arch.model
    g = m.grid(img_res=img_res)
    n_img = g[0] * g[1] * g[2]
    hd = m.d_model // m.num_heads
    q_shape = (batch, m.num_heads, m.txt_tokens + n_img, hd)
    return decision_cache.initial_state(
        q_shape, grid=g, cfg=dataclasses.replace(arch.ripple, policy=pol),
        grid_slice=(m.txt_tokens, n_img), num_layers=m.num_layers,
        dtype=compute_dtype)


# --- serving traffic helpers ----------------------------------------------------


def latent_shape_for(arch: ArchConfig, shape: ShapeSpec) -> Tuple[int, ...]:
    """Per-request latent shape (no batch dim) for one generate cell —
    the serving engine's bucket identity."""
    m = arch.model
    fam = arch.family
    res = shape.img_res
    if fam == "dit":
        lr = m.latent_res(res)
        return (lr, lr, m.in_channels)
    if fam in ("mmdit", "unet"):
        lr = res // 8
        return (lr, lr, m.in_channels)
    if fam == "vdit":
        g = m.grid(img_res=res)
        return (g[0] * m.t_patch, g[1] * m.patch, g[2] * m.patch,
                m.in_channels)
    raise ValueError(f"no latent shape for family {fam!r}")


def mixed_gen_shapes(arch: ArchConfig, *, smoke: bool = False,
                     base: Optional[ShapeSpec] = None):
    """Heterogeneous 'generate' cells for mixed-traffic serving: the base
    resolution/step count plus a half-resolution and a short-schedule
    variant (each its own engine bucket)."""
    if base is None:
        gens = [s for s in arch.shapes if s.kind == "generate"]
        base = gens[0] if gens else ShapeSpec(
            name="gen", kind="generate", img_res=64, batch=1, steps=4)
    if smoke:
        base = dataclasses.replace(base, img_res=64, steps=3)
    res_lo = max(base.img_res // 2, 32)
    steps_lo = max(base.steps // 2, 2)
    variants = [
        base,
        dataclasses.replace(base, name=f"{base.name}_r{res_lo}",
                            img_res=res_lo),
        dataclasses.replace(base, name=f"{base.name}_s{steps_lo}",
                            steps=steps_lo),
    ]
    seen, out = set(), []
    for s in variants:
        k = (s.img_res, s.steps)
        if k not in seen:
            seen.add(k)
            out.append(s)
    return tuple(out)


def mixed_request_stream(arch: ArchConfig, shapes, num_requests: int,
                         seed: int = 0, policy: Optional[str] = None,
                         reuse_every: Optional[int] = None,
                         stream_every: Optional[int] = None):
    """Round-robin (ShapeSpec, GenRequest) traffic over ``shapes`` with
    deterministic per-request text embeddings and seeds.  ``policy``
    stamps every request with that reuse-policy name, ``reuse_every``
    with that decision-cache cadence, ``stream_every`` with that
    chunked-streaming cadence (each its own engine bucket dimension).
    Deadlines are *not* stamped here — an SLO is relative to submit
    time, so callers stamp ``deadline_s`` when they actually submit
    (``launch.serve``, ``benchmarks.serve_mixed``)."""
    from repro.serving.engine import GenRequest

    m = arch.model
    txt_dim = getattr(m, "txt_dim", getattr(m, "ctx_dim", 64))
    txt_tokens = getattr(m, "txt_tokens", getattr(m, "ctx_tokens", 8))
    out = []
    for i in range(num_requests):
        sp = shapes[i % len(shapes)]
        txt = 0.05 * np.random.default_rng(seed + i).standard_normal(
            (txt_tokens, txt_dim)).astype(np.float32)
        out.append((sp, GenRequest(
            request_id=i, txt=txt, steps=sp.steps, seed=seed + i,
            latent_shape=latent_shape_for(arch, sp), policy=policy,
            reuse_every=reuse_every, stream_every=stream_every)))
    return out


def _cfg_factor(arch: ArchConfig) -> int:
    # flux-dev is guidance-distilled (guidance embedding, single pass);
    # DiT / UNet / vDiT sample with classifier-free guidance (x2 batch).
    return 1 if arch.family == "mmdit" else 2


def _gen_spec(mesh, shape, rules):
    """Batch dim over whatever 'batch' resolved to; spatial dims get the
    'seq' axes if they divide (sequence parallelism for small batches)."""
    if mesh is None:
        return P()
    b_axes = rules.get("batch", ())
    s_axes = rules.get("seq", ())
    entries = [b_axes if b_axes else None]
    placed = False
    for dim in shape[1:]:
        if not placed and s_axes and dim % shlib.axis_size(mesh, s_axes) == 0:
            entries.append(s_axes)
            placed = True
        else:
            entries.append(None)
    return P(*entries)


# --- vision workloads ----------------------------------------------------------


def _vision_train(arch: ArchConfig, shape: ShapeSpec, mesh) -> Workload:
    tc = arch.train
    defs = model_fns(arch)
    ctx = ShardCtx(mesh, shlib.train_act_rules(mesh))
    m = arch.model
    fam = arch.family

    def loss_fn(params, batch, rng):
        if fam == "vit":
            logits = vit_lib.vit_apply(params, batch["images"], m, ctx=ctx,
                                       remat=tc.remat)
        else:
            logits = eff_lib.effnet_apply(params, batch["images"], m, ctx=ctx,
                                          remat=tc.remat)
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["labels"][:, None], 1)[:, 0]
        loss = jnp.mean(logz - gold)
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"])
                       .astype(jnp.float32))
        return loss, {"acc": acc}

    step = train_loop.make_train_step(loss_fn, tc)
    abstract_state, state_sh = _state_shardings(arch, defs, mesh, tc)
    B, res = shape.batch, shape.img_res
    batch = {"images": jax.ShapeDtypeStruct((B, res, res, 3), jnp.float32),
             "labels": jax.ShapeDtypeStruct((B,), jnp.int32)}
    bsh = {k: _batch_sharding(mesh, v.ndim, size0=v.shape[0])
           for k, v in batch.items()}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    trips = m.num_layers if fam == "vit" else 0
    return Workload(
        arch=arch, shape=shape, mesh=mesh, fn=step,
        args=(abstract_state, batch, rng),
        in_shardings=(state_sh, bsh, _named(mesh, P())),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
        loop_trips=trips, probe="two_point" if trips else "none")


def _vision_serve(arch: ArchConfig, shape: ShapeSpec, mesh) -> Workload:
    defs = model_fns(arch)
    m = arch.model
    fam = arch.family
    B, res = shape.batch, shape.img_res
    bd_size = 1 if mesh is None else shlib.axis_size(
        mesh, shlib.batch_axes(mesh))
    rules = shlib.train_act_rules(mesh)
    if B % max(bd_size, 1) != 0:
        rules["batch"] = ()   # latency cell: model-parallel only
    ctx = ShardCtx(mesh, rules)

    def fn(params, images):
        if fam == "vit":
            return vit_lib.vit_apply(params, images, m, ctx=ctx)
        return eff_lib.effnet_apply(params, images, m, ctx=ctx)

    ap, psh = _param_shardings(defs, mesh)
    images = jax.ShapeDtypeStruct((B, res, res, 3), jnp.float32)
    img_spec = P(rules["batch"] if rules["batch"] else None)
    trips = m.num_layers if fam == "vit" else 0
    return Workload(
        arch=arch, shape=shape, mesh=mesh, fn=fn,
        args=(ap, images),
        in_shardings=(psh, _named(mesh, img_spec)),
        out_shardings=None,
        loop_trips=trips, probe="two_point" if trips else "none")


# --- entry point -----------------------------------------------------------------


def build_workload(arch: ArchConfig, shape_name: str,
                   mesh: Optional[Mesh]) -> Workload:
    shape = arch.shape(shape_name)
    fam = arch.family
    kind = shape.kind
    if fam == "lm":
        if kind == "train":
            return _lm_train(arch, shape, mesh)
        if kind == "prefill":
            return _lm_prefill(arch, shape, mesh)
        if kind == "decode":
            return _lm_decode(arch, shape, mesh)
    elif fam in ("dit", "mmdit", "unet", "vdit"):
        if kind == "train":
            return _diffusion_train(arch, shape, mesh)
        if kind == "generate":
            return _diffusion_generate(arch, shape, mesh)
    elif fam in ("vit", "effnet"):
        if kind == "train":
            return _vision_train(arch, shape, mesh)
        if kind == "classify":
            return _vision_serve(arch, shape, mesh)
    raise ValueError(f"no workload for family={fam} kind={kind}")
