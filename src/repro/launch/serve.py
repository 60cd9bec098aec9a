"""Serving launcher: bucketed continuous-batching diffusion generation
with TimeRipple on, optionally sharded over a device mesh.

``python -m repro.launch.serve --smoke`` spins up the DiffusionEngine on
a mixed-shape request stream (several (resolution, steps) buckets),
logs the resolved attention-dispatch plan per bucket, and reports
latency.  ``--shape NAME`` pins single-shape traffic instead;
``--mesh DxMxS`` (e.g. ``--mesh 4x2`` or ``--mesh 1x1x2``) installs a
(data, model[, seq]) mesh so the ripple/reuse-mask pipeline runs under
shard_map (DESIGN.md §10); a third component shards the token axis for
context-parallel ring attention (DESIGN.md §14) — on CPU prefix with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import os
import signal
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.config.base import apply_overrides
from repro.core import dispatch as dispatch_lib
from repro.core.policy import RippleStats, list_policies
from repro.diffusion.sampler import ddim_sample, euler_flow_sample
from repro.diffusion.schedule import DDPMSchedule
from repro.launch.mesh import parse_mesh_spec
from repro.launch.workloads import (STATS_FAMILIES, _denoise_call,
                                    attention_plan, latent_shape_for,
                                    mixed_gen_shapes, mixed_request_stream,
                                    model_fns, vdit_decision_state)
from repro.distributed.sharding import NULL_CTX
from repro.models.params import init_params
from repro.serving.engine import DiffusionEngine
from repro.serving.slo import ServiceEstimator, ShedError
from repro.utils.diskio import atomic_write_text
from repro.utils.logging import get_logger
from repro.utils.platform import place_compile_cache

log = get_logger("launch.serve")


def reuse_counters(stats: RippleStats) -> jax.Array:
    """The engine's ``COUNTERS`` (steps, 4) from per-step RippleStats,
    each the mean over the model's attention calls: one array, so the
    engine fetches every counter in one transfer."""
    return jnp.stack([stats.q_snap_frac, stats.k_snap_frac, stats.savings,
                      stats.structural_savings], axis=-1)


def build_sampler(arch, shape, params, *, use_ripple=True, policy=None,
                  reuse_every=None, stream_every=None, sentinel=False):
    """Returns sample_fn(noise, txt, rngs) -> latents (or ``(latents,
    aux)`` with telemetry) and the latent shape.
    ``rngs`` is the engine's (B, 2) per-request key batch: the initial
    noise is built outside from the same keys, and conditioning
    randomness (DiT labels) is drawn per request via vmap — no request
    in a batch ever shares sampler randomness.  ``policy`` overrides the
    arch config's reuse policy for this sampler (DESIGN.md §11);
    ``reuse_every`` its decision-cache cadence (DESIGN.md §13) — with a
    cadence > 1 (or the drift guard on) on a cache-capable vdit config,
    the per-layer decision state is threaded through the sampler's scan
    and the reuse decision is only recomputed on refresh steps.

    ``stream_every=K`` returns a *generator* sample_fn instead: the
    denoising scan runs in jitted K-step chunks (the samplers'
    ``step_offset``/``total_steps`` slicing, bitwise-identical math to
    the monolithic scan) and each chunk's latents are yielded as they
    land, so the engine can deliver intermediate frames and measure
    time-to-first-frame (DESIGN.md §15.3).  The decision-cache state
    crosses chunks through the generator's loop carry, so the cadence
    and drift guard behave exactly as in one scan.

    ``sentinel=True`` arms the in-graph quality sentinels (DESIGN.md
    §17): the samplers carry a running non-finite latent count
    (``aux["latent_nonfinite"]``) and, on cache-threading vdit configs,
    the dispatch layer accumulates per-call attention-output sentinels
    into the decision cache (``aux["sentinel_nonfinite"]`` /
    ``aux["sentinel_drift"]``) — the counters the engine's degradation
    ladder trips on.

    vdit and mmdit samplers always report the engine's reuse
    ``COUNTERS`` of every step they run: ``aux["counters"]`` is a
    (steps, 4) array computed on the device beside the step, for the
    engine to fetch with the latents (DESIGN.md §15.5).

    ``params`` enter every jitted program as an argument, never as a
    closed-over constant: at published widths the weights are gigabytes,
    and baking them into the program would copy them into the
    executable."""
    if sentinel:
        arch = dataclasses.replace(
            arch, ripple=dataclasses.replace(arch.ripple, sentinel=True))
    if policy:
        arch = dataclasses.replace(
            arch, ripple=dataclasses.replace(arch.ripple, policy=policy))
    if reuse_every is not None:
        arch = dataclasses.replace(
            arch, ripple=dataclasses.replace(arch.ripple,
                                             reuse_every=int(reuse_every)))
    m = arch.model
    fam = arch.family
    with_stats = fam in STATS_FAMILIES
    steps = shape.steps or 50
    lat_shape = latent_shape_for(arch, shape)
    from repro.core import decision_cache

    rip = arch.ripple
    thread_cache = (
        use_ripple and fam == "vdit"
        and (rip.reuse_every > 1 or rip.drift_tol > 0)
        and decision_cache.supports_cache(rip))
    sampler = (euler_flow_sample if fam == "mmdit" else
               functools.partial(ddim_sample, schedule=DDPMSchedule()))

    def make_cond(txt, rngs):
        if fam == "dit":
            labels = jax.vmap(
                lambda k: jax.random.randint(k, (), 0, m.num_classes))(rngs)
            return {"labels": labels}
        if fam == "mmdit":
            return {"txt": txt, "vec": jnp.zeros((txt.shape[0], 768))}
        if fam == "unet":
            return {"ctx": txt}
        return {"txt": txt}

    def cache_aux(dstate, aux):
        aux["cache_hits"] = dstate.hits.sum()
        aux["cache_refreshes"] = dstate.refreshes.sum()
        if dstate.elided is not None:
            # Ring-path telemetry (DESIGN.md §14): total ring hops
            # the block map let every seq shard skip this request.
            aux["ring_elided_hops"] = dstate.elided.sum()
        if dstate.nonfinite is not None:
            aux["sentinel_nonfinite"] = dstate.nonfinite.sum()
        if dstate.probe_err is not None:
            aux["sentinel_drift"] = dstate.probe_err.max()
        return aux

    def sample(params, x, txt, rngs, step0, dstate, count):
        """``count`` denoising steps from ``step0``: ``(x, dstate,
        non-finite count, counters)``, None for what is not tracked."""
        cond = make_cond(txt, rngs)

        def denoise(x, t, step, *ds):
            res = _denoise_call(
                arch, params, x, t, cond, step, steps, NULL_CTX,
                use_ripple=use_ripple, dstate=ds[0] if ds else None,
                with_stats=with_stats)
            if not (ds or with_stats):
                return res.astype(x.dtype)
            return (res[0].astype(x.dtype),) + tuple(res[1:])

        res = sampler(denoise, x, num_steps=count, decision_state=dstate,
                      step_offset=step0, total_steps=steps,
                      sentinel=sentinel, with_stats=with_stats)
        res = list(res) if isinstance(res, tuple) else [res]
        x = res.pop(0)
        dstate = res.pop(0) if dstate is not None else None
        nf = res.pop(0) if sentinel else None
        counters = reuse_counters(res.pop(0)) if with_stats else None
        return x, dstate, nf, counters

    if stream_every:
        K = max(int(stream_every), 1)

        @functools.partial(jax.jit, static_argnames=("count",))
        def chunk_fn(params, x, txt, rngs, step0, dstate, *, count):
            return sample(params, x, txt, rngs, step0, dstate, count)

        def sample_fn(noise, txt, rngs, resume=None):
            # Mid-flight resume (DESIGN.md §18): ``resume={"step": S,
            # "dstate": state}`` starts the chunk loop at offset S with
            # the checkpointed decision state; ``noise`` is then the
            # checkpointed x_t, not fresh noise.  Because checkpoints
            # land only at chunk boundaries, the resumed run replays
            # the exact chunk partitioning of the uninterrupted one —
            # the PR 7 chaining contract makes the result bitwise-equal.
            start = 0
            dstate = None
            if resume is not None:
                start = int(resume.get("step", 0))
                dstate = resume.get("dstate")
                if thread_cache and dstate is None and start > 0:
                    # A mid-flight start without the cached decision
                    # state would apply a zeroed plan at a non-refresh
                    # step; replaying from 0 is slower but exact.
                    start = 0
            if thread_cache and dstate is None:
                dstate = vdit_decision_state(arch, shape.img_res,
                                             noise.shape[0])
            x = noise
            nf_total = jnp.zeros((), jnp.int32)
            for s0 in range(start, steps, K):
                count = min(K, steps - s0)
                x, dstate, nf, counters = chunk_fn(
                    params, x, txt, rngs, jnp.asarray(s0, jnp.int32),
                    dstate, count=count)
                aux = {}
                if dstate is not None:
                    cache_aux(dstate, aux)
                if nf is not None:
                    # Per-chunk counts accumulate so the final chunk's
                    # aux reports the whole trajectory.
                    nf_total = nf_total + nf
                    aux["latent_nonfinite"] = nf_total
                if counters is not None:
                    aux["counters"] = counters
                # Chunk-boundary checkpoint state for the engine's
                # store (§18): the step offset the *next* chunk would
                # start from, plus the decision state that step needs.
                aux["__ckpt__"] = {"step": s0 + count, "dstate": dstate}
                yield x, aux

        return sample_fn, lat_shape

    @jax.jit
    def run(params, noise, txt, rngs):
        dstate = (vdit_decision_state(arch, shape.img_res, noise.shape[0])
                  if thread_cache else None)
        x, final, nf, counters = sample(params, noise, txt, rngs, 0,
                                        dstate, steps)
        aux = cache_aux(final, {}) if thread_cache else {}
        if sentinel:
            aux["latent_nonfinite"] = nf
        if counters is not None:
            aux["counters"] = counters
        return (x, aux) if aux else x

    return functools.partial(run, params), lat_shape


def make_sampler_factory(arch, shapes, params, *, use_ripple=True,
                         mesh=None, sentinel=False):
    """(engine sampler_factory, plan_fn) over a set of generate cells,
    keyed by the engine's (latent_shape, steps, policy, reuse_every,
    stream_every) bucket identity.  The engine hands both callables the
    bucket's reuse-policy name (None = the arch config's
    ``ripple.policy``) and the factory additionally its decision-cache
    cadence (None = the config's ``ripple.reuse_every``) and streaming
    cadence (None = monolithic delivery, DESIGN.md §15.3)."""
    by_bucket = {}
    for sp in shapes:
        by_bucket[(tuple(latent_shape_for(arch, sp)), sp.steps)] = sp

    def factory(latent_shape, steps, policy=None, reuse_every=None,
                stream_every=None):
        sp = by_bucket[(tuple(latent_shape), steps)]
        fn, _ = build_sampler(arch, sp, params, use_ripple=use_ripple,
                              policy=policy, reuse_every=reuse_every,
                              stream_every=stream_every,
                              sentinel=sentinel)
        return fn

    def plan_fn(latent_shape, steps, policy=None):
        sp = by_bucket[(tuple(latent_shape), steps)]
        return attention_plan(arch, sp, mesh=mesh, policy=policy)

    return factory, plan_fn


def _maybe_kill_replica(front, fault, completed: int):
    """Fire a ``kill_replica`` fault (DESIGN.md §17.3) once ``completed``
    results have been consumed: fail the deepest router replica so its
    pending requests demonstrably requeue onto survivors."""
    from repro.serving.router import Router

    if fault is None or not isinstance(front, Router):
        return
    spec = fault.spec("kill_replica")
    if spec is None or completed < int(spec.param("after", 1)):
        return
    if fault.take("kill_replica") is None:
        return
    depths = front.depths()
    if not depths:
        return
    idx = max(depths, key=depths.get)
    log.warning("fault injection: killing replica %d (depth %d)",
                idx, depths[idx])
    front.fail_replica(idx)


def _maybe_crash(fault, completed: int, *, store=None):
    """Fire a ``crash`` fault (DESIGN.md §18): SIGKILL this process —
    no drain, no clean-shutdown marker — once ``completed`` results have
    been consumed.  With ``wait_ckpt=1`` (default) and a checkpoint
    store attached, first block until at least one in-flight request
    has a chunk checkpoint on disk (entries are discarded at finish, so
    an existing entry *is* in-flight work), making "killed
    mid-generation" deterministic instead of a race with the sampler."""
    if fault is None:
        return
    spec = fault.spec("crash")
    if spec is None or completed < int(spec.param("after", 1)):
        return
    if int(spec.param("wait_ckpt", 1)) and store is not None:
        deadline = time.time() + float(spec.param("wait_s", 60.0))
        while store.count() == 0 and time.time() < deadline:
            time.sleep(0.05)
        if store.count() == 0:
            log.warning("crash fault: no checkpoint landed within the "
                        "wait budget; killing anyway")
    if fault.take("crash") is None:
        return
    log.warning("fault injection: SIGKILL self (hard crash, no drain)")
    sys.stderr.flush()
    os.kill(os.getpid(), signal.SIGKILL)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vdit-paper", choices=ALL_ARCHS)
    ap.add_argument("--shape", default=None,
                    help="single-shape traffic from this named shape; "
                         "default: a mixed-shape request stream")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=None,
                    help="denoising steps for every traffic bucket "
                         "(default: each shape's own step count)")
    ap.add_argument("--mesh", default=None, metavar="DxMxS",
                    help="(data, model[, seq]) mesh, e.g. 8, 4x2 or "
                         "1x1x2; shards the attention dispatch under "
                         "shard_map.  A third component shards the token "
                         "axis for context-parallel ring attention "
                         "(DESIGN.md §14)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-compiled", type=int, default=8,
                    help="bounded LRU of per-bucket compiled samplers")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="serve through a Router over N in-process "
                         "engine replicas (DESIGN.md §15.4): least-"
                         "loaded balancing, failover requeue")
    ap.add_argument("--scheduler", default="edf",
                    choices=("edf", "hottest"),
                    help="bucket drain policy (DESIGN.md §15.1): "
                         "deadline-aware EDF (default) or the pre-SLO "
                         "hottest-bucket-first")
    ap.add_argument("--deadline-ms", type=float, default=None, metavar="MS",
                    help="stamp every request with a deadline of now+MS "
                         "at submit; infeasible requests are shed at "
                         "the door (DESIGN.md §15.2)")
    ap.add_argument("--stream-every", type=int, default=None, metavar="K",
                    help="chunked streaming delivery: yield decoded "
                         "latents every K denoising steps and report "
                         "time-to-first-frame (DESIGN.md §15.3)")
    ap.add_argument("--distributed", action="store_true",
                    help="initialize jax.distributed before touching "
                         "devices (multi-host fleet, DESIGN.md §15.4); "
                         "reads --coordinator/--num-processes/"
                         "--process-id")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--no-ripple", action="store_true")
    ap.add_argument("--policy", default=None,
                    help="reuse-policy name for every request (built-ins: "
                         "ripple, svg, equal_mse, dense; out-of-tree "
                         "policies register via --policy-module). "
                         "Default: the arch config's ripple.policy")
    ap.add_argument("--policy-module", default=None, metavar="MODULE",
                    help="import this python module before serving so it "
                         "can register_policy() an out-of-tree strategy")
    ap.add_argument("--reuse-every", type=int, default=None, metavar="R",
                    help="decision-cache cadence (DESIGN.md §13): "
                         "recompute the reuse decision every R denoising "
                         "steps and re-apply it in between; part of the "
                         "engine bucket key.  Default: the arch config's "
                         "ripple.reuse_every (1 = per-step decisions)")
    ap.add_argument("--drift-tol", type=float, default=None, metavar="TOL",
                    help="decision-cache drift guard: force an early "
                         "refresh when the sampled-channel Δ statistic "
                         "moves more than TOL (relative) from the cached "
                         "decision's reference.  0 disables (default: "
                         "the arch config's ripple.drift_tol)")
    ap.add_argument("--attn-backend", default=None,
                    choices=("auto", "dense", "reference", "collapse",
                             "pallas", "sparse"),
                    help="override RippleConfig.backend for the dispatch "
                         "layer (default: the arch config's setting)")
    ap.add_argument("--pattern-artifact", default=None, metavar="PATH",
                    help="install a searched pattern artifact "
                         "(launch/pattern_search.py) for the static / "
                         "rainfusion policies; errors if the file is "
                         "missing or corrupt.  Default: the "
                         "REPRO_PATTERN_ARTIFACT env var / user cache "
                         "(loaded lazily, missing file tolerated)")
    ap.add_argument("--no-guardrail", action="store_true",
                    help="disable the runtime quality guardrails "
                         "(DESIGN.md §17): in-graph NaN/drift sentinels "
                         "and the per-bucket degradation ladder.  On by "
                         "default")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="arm the deterministic chaos harness "
                         "(serving.faults, DESIGN.md §17.3), e.g. "
                         "'attn_nan:step=1;kill_replica:after=1'.  "
                         "Default: the REPRO_FAULTS env var")
    ap.add_argument("--batch-timeout", type=float, default=None,
                    metavar="S",
                    help="hang-watchdog floor per batch in seconds "
                         "(scaled by the service-time estimator once "
                         "observed); a hung batch marks the replica "
                         "unhealthy and its requests fail over.  "
                         "Default: no watchdog")
    ap.add_argument("--probe-interval", type=float, default=0.5,
                    metavar="S",
                    help="router health-probe cadence for re-admitting "
                         "recovered replicas (only with --replicas > 1)")
    ap.add_argument("--journal", default=None, metavar="DIR",
                    help="crash-safe serving (DESIGN.md §18): write the "
                         "request-lifecycle WAL, chunk-boundary "
                         "generation checkpoints, and the service-time "
                         "estimator snapshot under DIR.  SIGTERM drains "
                         "gracefully and leaves a clean-shutdown marker; "
                         "SIGKILL leaves a recoverable journal")
    ap.add_argument("--resume", action="store_true",
                    help="recover the journal directory's pending "
                         "requests (submitted, never finished/shed) and "
                         "resume any with a chunk checkpoint mid-flight "
                         "before serving new traffic; requires --journal")
    ap.add_argument("--journal-fsync", default="always",
                    choices=("always", "interval", "never"),
                    help="journal durability policy: fsync every append "
                         "(default), every few appends, or never (flush "
                         "only — survives SIGKILL but not power loss)")
    ap.add_argument("--checkpoint-max", type=int, default=64, metavar="N",
                    help="bound on distinct requests with an on-disk "
                         "generation checkpoint (least-recently-written "
                         "evicted first)")
    ap.add_argument("--summary-json", default=None, metavar="PATH",
                    help="write a machine-readable final summary "
                         "(completed/errors/recovered/resumed_from_step/"
                         "counters) to PATH — the crash-restart smoke's "
                         "assertion surface")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args(argv)
    cache_dir = place_compile_cache()
    log.info("compile cache: %s", cache_dir)

    if args.distributed:
        from repro.launch.mesh import init_distributed

        init_distributed(coordinator_address=args.coordinator,
                         num_processes=args.num_processes,
                         process_id=args.process_id)

    if args.policy_module:
        importlib.import_module(args.policy_module)
    if args.pattern_artifact is not None:
        from repro.core import patterns

        art = patterns.install_artifact(args.pattern_artifact)
        log.info("pattern artifact %s: %d heads, %.0f%% static",
                 art.version, len(art.heads),
                 100.0 * art.static_fraction())
    if args.policy is not None and args.policy not in list_policies():
        ap.error(f"unknown policy {args.policy!r}; registered: "
                 f"{list_policies()} (use --policy-module to register "
                 f"an out-of-tree policy first)")

    mesh = parse_mesh_spec(args.mesh) if args.mesh else None
    if mesh is not None:
        dispatch_lib.set_dispatch_mesh(mesh)
        log.info("dispatch mesh: %s", dict(mesh.shape))

    arch = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    arch = apply_overrides(arch, args.overrides)
    if args.attn_backend is not None:
        arch = dataclasses.replace(
            arch, ripple=dataclasses.replace(arch.ripple,
                                             backend=args.attn_backend))
    if args.drift_tol is not None:
        arch = dataclasses.replace(
            arch, ripple=dataclasses.replace(arch.ripple,
                                             drift_tol=args.drift_tol))

    if args.shape is not None:
        shapes = (arch.shape(args.shape),)
    else:
        shapes = mixed_gen_shapes(arch, smoke=args.smoke)
    if args.steps is not None:
        shapes = tuple(dataclasses.replace(s, steps=args.steps)
                       for s in shapes)
    log.info("traffic buckets: %s",
             [(s.name, s.img_res, s.steps) for s in shapes])

    from repro.serving import faults as fault_lib

    if args.inject_faults:
        fault_lib.install_faults(args.inject_faults)
    else:
        fault_lib.install_from_env()
    fault = fault_lib.active_faults()

    guardrail = not args.no_guardrail
    ladder = None
    if guardrail:
        from repro.core.guardrail import DegradationLadder

        # One ladder shared across every replica: degraded-bucket state
        # survives a replica failover (DESIGN.md §17.2).
        ladder = DegradationLadder()

    # -- crash-safety state (DESIGN.md §18) ---------------------------------
    if args.resume and not args.journal:
        ap.error("--resume requires --journal DIR")
    journal = store = None
    estimator = None
    recovered = []
    rid_base = 0
    est_path = None
    if args.journal:
        from repro.serving import journal as journal_lib

        # Scan *before* opening: Journal() removes the clean marker.
        rec = journal_lib.recover(args.journal)
        if rec.events:
            log.info("journal %s: %d event(s), %d pending, clean=%s, "
                     "torn_tail=%s", args.journal, rec.events,
                     len(rec.pending), rec.clean, rec.torn)
        journal = journal_lib.Journal(args.journal,
                                      fsync=args.journal_fsync)
        store = journal_lib.CheckpointStore(
            args.journal, max_entries=args.checkpoint_max,
            fsync=args.journal_fsync != "never")
        est_path = os.path.join(args.journal, "estimator.json")
        if os.path.exists(est_path):
            try:
                with open(est_path, "r", encoding="utf-8") as f:
                    estimator = ServiceEstimator.from_json(f.read())
                log.info("restored service-time estimator from %s",
                         est_path)
            except (OSError, ValueError):
                log.warning("could not restore estimator from %s; "
                            "starting cold", est_path)
        # New request ids must never collide with journaled history —
        # a reused id would alias lifecycle records across requests.
        known = (set(rec.pending) | set(rec.finished) | set(rec.shed))
        rid_base = max(known, default=-1) + 1
        if args.resume and rec.pending:
            if not rec.clean:
                log.warning("crash detected (no matching clean-shutdown "
                            "marker): recovering %d pending request(s)",
                            len(rec.pending))
            for rid, reqd in sorted(rec.pending.items()):
                try:
                    req = journal_lib.request_from_dict(reqd)
                except (KeyError, ValueError, TypeError):
                    log.exception("journaled request %s is unreadable; "
                                  "skipping", rid)
                    continue
                # The absolute deadline has almost certainly expired
                # across the restart; shedding a journaled request at
                # the recovery door would break the every-journaled-
                # request-completes contract.
                req.deadline_s = None
                req.recovered = True
                ck = store.get(rid) if req.stream_every else None
                if ck and 0 < ck["step"] < req.steps \
                        and ck["step"] % req.stream_every == 0:
                    req.resume = {"step": ck["step"], "x": ck["x"],
                                  "dstate": ck.get("dstate")}
                    log.info("request %d resumes from step %d/%d",
                             rid, ck["step"], req.steps)
                recovered.append(req)
    if estimator is None and args.journal:
        estimator = ServiceEstimator()

    defs = model_fns(arch)
    params = init_params(defs, jax.random.PRNGKey(args.seed))
    factory, plan_fn = make_sampler_factory(
        arch, shapes, params, use_ripple=not args.no_ripple, mesh=mesh,
        sentinel=guardrail)

    def make_engine():
        return DiffusionEngine(sampler_factory=factory,
                               max_batch=args.max_batch,
                               max_compiled=args.max_compiled,
                               plan_fn=plan_fn,
                               default_policy=args.policy,
                               default_reuse_every=args.reuse_every,
                               scheduler=args.scheduler,
                               guardrail=ladder,
                               batch_timeout_s=args.batch_timeout,
                               estimator=estimator,
                               journal=journal,
                               checkpoint_store=store)

    if args.replicas > 1:
        from repro.serving.router import Router

        front = Router([make_engine() for _ in range(args.replicas)],
                       probe_interval_s=args.probe_interval,
                       checkpoint_store=store)
    else:
        front = make_engine()
    front.start()
    traffic = mixed_request_stream(arch, shapes, args.requests,
                                   seed=args.seed, policy=args.policy,
                                   reuse_every=args.reuse_every,
                                   stream_every=args.stream_every)
    terminating = {"sigterm": False}
    if args.journal:
        def _graceful(signum, frame):
            # Graceful drain (§18): queued requests stay journaled-
            # pending, in-flight chunks are already checkpointed; the
            # finally block below stops without drain and writes the
            # clean-shutdown marker.
            terminating["sigterm"] = True
            log.warning("SIGTERM: graceful drain — pending work stays "
                        "journaled for --resume")
            raise SystemExit(143)

        signal.signal(signal.SIGTERM, _graceful)
    t0 = time.time()
    shed = 0
    submitted = []
    completed = []
    errors = {}
    degraded = []
    walltimes = {}
    reuse = {}
    latents = {}
    try:
        for req in recovered:
            sp = types.SimpleNamespace(name="recovered", steps=req.steps)
            try:
                front.submit(req)
            except ShedError as e:
                shed += 1
                log.warning("%s", e)
                continue
            submitted.append((sp, req))
        for sp, req in traffic:
            req.request_id += rid_base
            if args.deadline_ms is not None:
                req.deadline_s = time.time() + args.deadline_ms / 1e3
            try:
                front.submit(req)
            except ShedError as e:
                shed += 1
                log.warning("%s", e)
                continue
            submitted.append((sp, req))
        for done, (sp, req) in enumerate(submitted):
            _maybe_kill_replica(front, fault, done)
            _maybe_crash(fault, done, store=store)
            try:
                r = front.result(req.request_id)
            except (RuntimeError, TimeoutError) as e:
                errors[req.request_id] = str(e)
                log.error("request %d failed: %s", req.request_id, e)
                continue
            if not np.isfinite(np.asarray(r.latents)).all():
                errors[req.request_id] = "non-finite latents"
                log.error("request %d returned non-finite latents",
                          req.request_id)
                continue
            completed.append(req.request_id)
            walltimes[req.request_id] = r.walltime_s
            if r.reuse is not None:
                reuse[req.request_id] = r.reuse
            latents[req.request_id] = r.latents
            if r.degraded:
                degraded.append(req.request_id)
            log.info("request %d (%s, %d steps) done in %.2fs "
                     "(ttff %.3fs%s%s%s); latents %s",
                     req.request_id, sp.name, sp.steps, r.walltime_s,
                     r.ttff_s,
                     "" if r.deadline_met is None
                     else f", deadline "
                          f"{'met' if r.deadline_met else 'MISSED'}",
                     ", DEGRADED" if r.degraded else "",
                     ", RECOVERED" if req.recovered else "",
                     r.latents.shape)
    except SystemExit:
        if not terminating["sigterm"]:
            raise
    finally:
        front.stop(drain=not terminating["sigterm"])
        if journal is not None:
            journal.close(clean=True)
            if estimator is not None and est_path is not None:
                atomic_write_text(est_path, estimator.to_json())
    counters = dict(front.metrics()) if hasattr(front, "metrics") else {}
    if fault is not None:
        counters.update(fault.counters())
    if ladder is not None:
        counters.update(ladder.metrics())
    if counters:
        log.info("serving counters: %s", counters)
    resumed_from = max(
        [int(v) for k, v in counters.items()
         if k.endswith("last_resume_step")] or [0])
    log.info("served %d/%d requests (%d shed, %d recovered, deepest "
             "resume step %d) over %d bucket(s) in %.2fs total",
             len(completed), args.requests + len(recovered), shed,
             len(recovered), resumed_from, len(shapes),
             time.time() - t0)
    summary = {
        "submitted": [req.request_id for _, req in submitted],
        "completed": completed,
        "errors": {str(k): v for k, v in errors.items()},
        "degraded": degraded,
        "walltime_s": {str(k): v for k, v in walltimes.items()},
        "reuse": {str(k): v for k, v in reuse.items()},
        "shed": shed,
        "recovered": len(recovered),
        "resumed_from_step": resumed_from,
        "sigterm": terminating["sigterm"],
        "counters": {k: (float(v) if isinstance(v, float) else int(v))
                     for k, v in counters.items()},
    }
    if args.summary_json:
        with open(args.summary_json, "w", encoding="utf-8") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
        log.info("wrote summary to %s", args.summary_json)
    if terminating["sigterm"]:
        sys.exit(143)
    # A failed request fails the run; so does a guardrail degradation
    # unless faults were injected on purpose (the chaos drill expects
    # the ladder to step down).
    if errors or (degraded and fault is None):
        log.error("%d request(s) failed, %d degraded", len(errors),
                  len(degraded))
        sys.exit(1)
    summary["latents"] = latents
    return summary


if __name__ == "__main__":
    main()
