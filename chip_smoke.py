#!/usr/bin/env python3
"""Bring-up smoke test: serve the paper's vDiT on a TPU through the
normal entry point and check what comes back.

    python chip_smoke.py            # one chip: attention check, ripple, svg
    python chip_smoke.py --chips 4  # only the --mesh 1x4 phase and its
                                    # one-device reference

``vdit-paper`` runs at its published widths (d_model 3072, 24 heads of
128, 256 text tokens of width 4096, the ``gen_512`` latent of 32x64x64x16,
i.e. 32,768 video tokens) with random weights from a seed.  Only depth,
step count and the Eq. 4 ramp are cut, through the launcher's own
``overrides``; every cut is printed before it is used.

Phases (one process; each served phase calls ``repro.launch.serve.main``):

* ``attention`` — at N = 33,024: the ripple kernel behind
  ``attention_dispatch`` with nothing snapped (θ = 0) against
  ``flash_attention`` and against a query-sliced float32 reference;
  at θ_max the fused Δ-check kernel against the host ``compute_reuse``
  (bitwise) and the dispatched output against the ripple kernel on the
  host-snapped operands (bitwise) and flash attention on them; the
  block-sparse kernel on a random tile map against the masked
  reference.
* ``ripple`` — two requests of the default ``ripple`` policy, ``auto``
  backend: every plan must resolve to ``pallas`` with the fused Δ-check,
  and each request must report snapped q and k entries
  (``--reuse-stats``).
* ``svg`` — one ``--policy svg`` request: every plan must be ``sparse``.

Any failed, shed, degraded or non-finite request, a wrong plan, a
mismatch, or a platform other than ``tpu`` exits non-zero without the
final line.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Cuts of the published configuration (depth, steps, ramp) and why.
LAYERS = 8      # of 40: 8.35 B parameters are 33 GB in f32; 8 layers
                # hold 6.7 GB and leave room for 33k-token activations
STEPS = 6       # of 50
RAMP = ("ripple.i_min=1", "ripple.i_max=3")  # of 10..20: reuse from step 1
# DESIGN.md §10: the served model under a head-sharded mesh agrees with
# one device to this max-norm share (the output projection's partial
# sums are all-reduced in bf16 in another order); attention itself is
# bitwise-equal.
MESH_TOL = 1e-2
# --chips 4 serves the request twice (one device, then the 1x4 mesh) at
# four chips' cost; two layers already run the head-sharded attention
# and the all-reduced output projection that the comparison is about,
# and four steps leave three past the ramp's dense first step.
MESH_LAYERS = 2
MESH_STEPS = 4
SVG_FRAMES = 16  # of 128: the svg policy materializes an (N, N) logit
                 # bias per head; at 16 frames (4 latent frames, 4,352
                 # tokens) it takes 1.8 GB instead of 105 GB


class SmokeFailure(Exception):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def say(*a):
    print(*a, flush=True)


def peak_memory() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.2f} GiB"


def serve_argv(*, policy=None, layers=LAYERS, steps=STEPS, requests=2,
               extra=(), overrides=()):
    argv = ["--arch", "vdit-paper", "--shape", "gen_512",
            "--steps", str(steps), "--requests", str(requests),
            "--max-batch", "1", "--seed", "0", *extra]
    if policy:
        argv += ["--policy", policy]
    return argv + [f"model.num_layers={layers}", *RAMP, *overrides]


def resolved_plans(dispatch, policy: str):
    """Every plan the traced programs resolved for ``policy``."""
    return [p for p in dispatch._PLAN_CACHE.values() if p.policy == policy]


def run_serve(serve, dispatch, argv, label):
    say(f"[{label}] python -m repro.launch.serve {' '.join(argv)}")
    dispatch.clear_plan_cache()
    t0 = time.perf_counter()
    try:
        summary = serve.main(argv)
    except SystemExit as e:
        raise SmokeFailure(f"{label}: launcher exited with {e.code}")
    wall = time.perf_counter() - t0
    check(not summary["errors"], f"{label}: errors {summary['errors']}")
    check(summary["shed"] == 0, f"{label}: {summary['shed']} shed")
    check(not summary["degraded"], f"{label}: degraded "
          f"{summary['degraded']}")
    check(len(summary["completed"]) == len(summary["submitted"]) > 0,
          f"{label}: completed {summary['completed']}")
    times = [summary["walltime_s"][str(r)] for r in summary["completed"]]
    say(f"[{label}] requests done in {[round(t, 3) for t in times]} s "
        f"(the first includes compilation); phase wall {wall:.1f} s")
    if len(times) > 1:
        say(f"[{label}] compile seconds ~ {times[0] - times[-1]:.1f} "
            f"(first request minus warm request)")
    say(f"[{label}] serving counters: {summary['counters']}")
    return summary


def attention_check(arch, img_res: int):
    """The attention kernels at the configuration's widths, against each
    other and a query-sliced float32 reference (never the full N×N)."""
    from repro.core.dispatch import attention_dispatch, resolve_plan
    from repro.core.reuse import compute_reuse
    from repro.kernels.flash.ops import flash_attention
    from repro.kernels.reuse_mask.ops import fused_compute_reuse
    from repro.kernels.ripple.ops import ripple_attention_pallas
    from repro.kernels.sparse.ops import (FULL, SKIP, sparse_attention_pallas,
                                          sparse_grid)

    m = arch.model
    grid = m.grid(img_res=img_res)
    n_img = grid[0] * grid[1] * grid[2]
    N, H, d = m.txt_tokens + n_img, m.num_heads, m.d_model // m.num_heads
    say(f"[attention] B=1 H={H} d={d} N={N} (grid {grid} + "
        f"{m.txt_tokens} text tokens), bf16")
    kq, kk, kv, km = jax.random.split(jax.random.PRNGKey(0), 4)

    def smooth(key):
        # Latent-like operands: a field constant over 2x2x2 grid cells
        # plus small noise, so θ_max snaps a real share of the tokens.
        k1, k2, k3 = jax.random.split(key, 3)
        T, Hg, Wg = grid
        base = jax.random.normal(k1, (1, H, T // 2, Hg // 2, Wg // 2, d))
        for ax in (2, 3, 4):
            base = jnp.repeat(base, 2, axis=ax)
        g = base.reshape(1, H, n_img, d) \
            + 0.05 * jax.random.normal(k2, (1, H, n_img, d))
        txt = jax.random.normal(k3, (1, H, m.txt_tokens, d))
        return jnp.concatenate([txt, g], axis=2).astype(jnp.bfloat16)

    q, k = smooth(kq), smooth(kk)
    v = jax.random.normal(kv, (1, H, N, d), jnp.bfloat16)
    cfg = arch.ripple
    zero = {a: jnp.zeros((), jnp.float32) for a in ("t", "x", "y")}
    kw = dict(grid=grid, cfg=cfg, grid_slice=(m.txt_tokens, n_img))

    t0 = time.perf_counter()
    rip = jax.block_until_ready(attention_dispatch(q, k, v, thetas=zero,
                                                   **kw))
    fl = jax.block_until_ready(flash_attention(q, k, v))
    say(f"[attention] dispatch(θ=0) + flash, compile and run: "
        f"{time.perf_counter() - t0:.1f} s")
    rows = jnp.asarray(list(range(0, N, max(N // 256, 1)))[:256])
    f32 = jnp.float32

    def reference(key_mask=None):
        """softmax(q k^T / sqrt(d)) v in f32 for the sampled rows."""
        with jax.default_matmul_precision("highest"):
            logits = jnp.einsum("bhqd,bhkd->bhqk",
                                q[:, :, rows].astype(f32),
                                k.astype(f32)) / jnp.sqrt(f32(d))
            if key_mask is not None:
                logits = jnp.where(key_mask, logits, -jnp.inf)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1),
                              v.astype(f32))

    ref = reference()
    scale = float(jnp.max(jnp.abs(ref)))

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))) / scale

    e_rf, e_rr, e_fr = (err(rip, fl), err(rip[:, :, rows], ref),
                        err(fl[:, :, rows], ref))
    say(f"[attention] max|err|/max|ref|: ripple-vs-flash {e_rf:.2e}, "
        f"ripple-vs-ref {e_rr:.2e}, flash-vs-ref {e_fr:.2e} "
        f"({len(rows)} query rows x {N} keys)")
    tol = 2e-2  # bf16 operands and outputs, f32 accumulation
    check(max(e_rf, e_rr, e_fr) <= tol,
          f"attention mismatch above {tol}: {e_rf}, {e_rr}, {e_fr}")

    # θ_max: the fused Δ-check kernel must snap exactly as the host
    # pipeline does, and the dispatched output must then be the ripple
    # kernel's on the host-snapped operands, bit for bit.
    th = {a: jnp.asarray(cfg.theta_max, f32) for a in ("t", "x", "y")}
    plan = resolve_plan(q.shape, v.shape, cfg, grid=grid,
                        grid_slice=(m.txt_tokens, n_img))
    check(plan.backend == "pallas" and plan.fused_mask,
          f"θ_max plan is {plan.summary()}, not pallas with the fused mask")
    out, stats = attention_dispatch(q, k, v, thetas=th, with_stats=True,
                                    **kw)
    say(f"[attention] θ={cfg.theta_max}: savings "
        f"{float(stats.savings):.3f}, structural "
        f"{float(stats.structural_savings):.3f}, q snapped "
        f"{float(stats.q_snap_frac):.3f}, k snapped "
        f"{float(stats.k_snap_frac):.3f}")
    check(bool(jnp.isfinite(out).all()), "non-finite snapped attention")
    seg = slice(m.txt_tokens, N)
    snap = []
    for name, x in (("q", q), ("k", k)):
        host = compute_reuse(x[:, :, seg], grid, th, axes=cfg.axes,
                             window=cfg.window, granularity=cfg.granularity,
                             channel_groups=cfg.channel_groups)
        f_snap, f_mask = fused_compute_reuse(x[:, :, seg], grid, th,
                                             axes=cfg.axes,
                                             granularity=cfg.granularity)
        same_v = bool(jnp.array_equal(f_snap, host.snapped))
        same_m = bool(jnp.array_equal(f_mask, host.mask))
        say(f"[attention] fused Δ-check vs host compute_reuse on {name}: "
            f"snapped values bitwise equal {same_v}, masks equal {same_m} "
            f"({float(jnp.mean(host.mask)):.3f} snapped)")
        check(same_v and same_m, f"fused Δ-check differs from the host "
              f"pipeline on {name}")
        snap.append(x.at[:, :, seg].set(host.snapped))
    rs = ripple_attention_pallas(*snap, v, window=cfg.window,
                                 block_q=plan.block_q, block_k=plan.block_k)
    fs = flash_attention(*snap, v)
    same = bool(jnp.array_equal(out, rs))
    e = err(rs, fs)
    say(f"[attention] dispatched (fused mask) vs ripple kernel on "
        f"host-snapped operands: bitwise equal {same}, max|diff|/max|ref| "
        f"{err(out, rs):.2e}; ripple kernel vs flash on them {e:.2e}")
    check(same, "dispatched attention differs from the ripple kernel on "
          "the host-snapped operands")
    check(e <= tol, f"snapped ripple-vs-flash mismatch {e}")

    # Block-sparse kernel on a random SKIP/FULL map over every head: at
    # this size its tile table spans several row-chunked calls.
    bq, bk, nq, nk = sparse_grid(N, N, 128, 128)
    bmap = jnp.where(jax.random.bernoulli(km, 0.5, (1, H, nq, nk)),
                     FULL, SKIP)
    bmap = jnp.where(jnp.eye(nq, nk, dtype=bool), FULL, bmap) \
        .astype(jnp.int32)
    sp = sparse_attention_pallas(q, k, v, block_map=bmap, block_q=bq,
                                 block_k=bk)
    keep = bmap[:, :, rows // bq][..., jnp.arange(N) // bk] == FULL
    e = err(sp[:, :, rows], reference(keep))
    say(f"[attention] sparse kernel, random block map ({nq}x{nk} tiles "
        f"of {bq}x{bk}, {float(jnp.mean(bmap == SKIP)):.3f} skipped) vs "
        f"the masked f32 reference: {e:.2e}")
    check(bool(jnp.isfinite(sp).all()) and e <= tol,
          f"sparse kernel mismatch {e}")


def one_chip(serve, dispatch):
    say(f"cuts: model.num_layers={LAYERS} of 40; steps {STEPS} of 50; "
        f"{' '.join(RAMP)} (paper 10..20, which a {STEPS}-step run would "
        f"never leave); svg phase model.frames={SVG_FRAMES} of 128")
    from repro.configs import get_config
    from repro.config.base import apply_overrides

    attention_check(apply_overrides(get_config("vdit-paper"), RAMP), 512)
    gc.collect()
    live_weights(serve)

    from repro.kernels.reuse_mask import ops as mask_ops

    fused_calls = []
    fused = mask_ops.fused_compute_reuse

    def counted(*a, **kw):
        fused_calls.append(kw.get("granularity"))
        return fused(*a, **kw)

    mask_ops.fused_compute_reuse = counted
    try:
        summary = run_serve(serve, dispatch,
                            serve_argv(extra=("--reuse-stats",)), "ripple")
    finally:
        mask_ops.fused_compute_reuse = fused
    plans = resolved_plans(dispatch, "ripple")
    for p in plans:
        say(f"[ripple] plan: {p.summary()}")
    check(plans and all(p.backend == "pallas" and p.fused_mask
                        and not p.tuned for p in plans),
          "ripple plans are not all untuned pallas with the fused mask")
    say(f"[ripple] fused Δ-check kernel traced {len(fused_calls)} time(s)")
    check(fused_calls, "the fused Δ-check kernel was not used")
    for rid in summary["completed"]:
        r = summary["reuse"].get(str(rid), {})
        say(f"[ripple] request {rid} reuse, means over {STEPS} steps x "
            f"{LAYERS} layers of attention: savings "
            f"{r.get('reuse_savings', 0.0):.4f}, structural "
            f"{r.get('reuse_structural_savings', 0.0):.4f}, q snapped "
            f"{r.get('reuse_q_snap_frac', 0.0):.4f}, k snapped "
            f"{r.get('reuse_k_snap_frac', 0.0):.4f}; decision cache "
            f"{r.get('cache_hits', 0)} hits / "
            f"{r.get('cache_refreshes', 0)} refreshes")
        check(r.get("reuse_q_snap_frac", 0.0) > 0
              and r.get("reuse_k_snap_frac", 0.0) > 0,
              f"ripple request {rid} snapped nothing")
    say(f"[ripple] peak device memory {peak_memory()}")
    gc.collect()

    run_serve(serve, dispatch, serve_argv(
        policy="svg", requests=1,
        overrides=(f"model.frames={SVG_FRAMES}",)), "svg")
    plans = resolved_plans(dispatch, "svg")
    for p in plans:
        say(f"[svg] plan: {p.summary()}")
    check(plans and all(p.backend == "sparse" and not p.tuned
                        for p in plans),
          "svg plans are not all untuned sparse")
    say(f"[svg] peak device memory {peak_memory()}")


def live_weights(serve):
    """Seeded random weights whose output depends on attention.

    ``init_params`` follows the published DiT init: the adaLN-zero
    gates and the final projection start at zero, so a fresh model
    returns zeros whatever its attention computes.  Every all-zero leaf
    is redrawn as N(0, 0.02²) from the same seed, the way a trained
    model's gates are nonzero; the launcher is otherwise untouched."""
    init = serve.init_params

    def init_params(defs, key):
        params = init(defs, key)
        leaves, tree = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
        leaves = [x if bool(x.any())
                  else 0.02 * jax.random.normal(k, x.shape, x.dtype)
                  for x, k in zip(leaves, keys)]
        return jax.tree.unflatten(tree, leaves)

    serve.init_params = init_params
    say("weights: seed 0; all-zero leaves (adaLN-zero gates, final "
        "projection) redrawn N(0, 0.02^2) so outputs depend on attention")


def four_chips(serve, dispatch):
    """Under --mesh 1x4 (24 heads, 6 per chip) against one device of
    the same process: the attention dispatch at N = 33,024 must be
    bitwise-equal (DESIGN.md §10), and the served latents must agree to
    the tolerance §10 documents for the whole model."""
    from repro.configs import get_config
    from repro.config.base import apply_overrides
    from repro.core.dispatch import attention_dispatch, dispatch_mesh
    from repro.launch.mesh import parse_mesh_spec

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, have "
          f"{len(jax.devices())}")
    say(f"cuts: model.num_layers={MESH_LAYERS} of 40; steps {MESH_STEPS} "
        f"of 50; {' '.join(RAMP)}")
    mesh = parse_mesh_spec("1x4")
    arch = apply_overrides(get_config("vdit-paper"), RAMP)
    m = arch.model
    grid = m.grid(img_res=512)
    n_img = grid[0] * grid[1] * grid[2]
    N, H, d = m.txt_tokens + n_img, m.num_heads, m.d_model // m.num_heads
    q, k, v = (jax.random.normal(kk, (1, H, N, d), jnp.bfloat16)
               for kk in jax.random.split(jax.random.PRNGKey(0), 3))
    th = {a: jnp.asarray(arch.ripple.theta_max, jnp.float32)
          for a in ("t", "x", "y")}

    def attend():
        # A fresh function per call: the mesh is read at trace time.
        return jax.jit(lambda q, k, v: attention_dispatch(
            q, k, v, grid=grid, cfg=arch.ripple, thetas=th,
            grid_slice=(m.txt_tokens, n_img)))

    a1 = np.asarray(attend()(q, k, v))
    with dispatch_mesh(mesh):
        a4 = np.asarray(attend()(q, k, v))
    same = bool(np.array_equal(a1, a4))
    say(f"[attention] N={N} H={H}: mesh 1x4 vs one device bitwise equal "
        f"{same}, max|diff| {float(np.max(np.abs(a1 - a4))):.3e}")
    check(same, "sharded attention differs from one device")

    live_weights(serve)
    argv = serve_argv(layers=MESH_LAYERS, steps=MESH_STEPS, requests=1)
    one = run_serve(serve, dispatch, argv, "one-device")
    four = run_serve(serve, dispatch, argv + ["--mesh", "1x4"], "mesh1x4")
    plans = resolved_plans(dispatch, "ripple")
    for p in plans:
        say(f"[mesh1x4] plan: {p.summary()}")
    check(plans and all(p.head_shards == 4 for p in plans),
          "mesh plans do not shard the heads 4 ways")
    a = np.asarray(one["latents"][one["completed"][0]], np.float32)
    b = np.asarray(four["latents"][four["completed"][0]], np.float32)
    rel = float(np.max(np.abs(a - b))) / float(np.max(np.abs(a)))
    say(f"[mesh1x4] latents {a.shape}: bitwise equal "
        f"{bool(np.array_equal(a, b))}, max|diff|/max|latent| {rel:.3e}")
    check(rel <= MESH_TOL, f"mesh 1x4 latents differ by {rel} > {MESH_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository sources at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    # Untuned default blocks only: no block-size autotune file from
    # this machine's past (it would sit under ~/.cache) steers a plan,
    # and nothing here writes one (the named file never exists).
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
        HERE, "untuned.autotune.json")
    from repro.utils.platform import place_compile_cache, platform

    cache = place_compile_cache()
    plat = platform()
    if plat != "tpu":
        print(f"chip_smoke: platform is {plat!r}, not 'tpu'; refusing to "
              f"run the kernels in interpret mode", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"device: {device}; jax {jax.__version__}; compile cache {cache} "
        f"({entries} entries)")

    from repro.core import dispatch
    from repro.launch import serve

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(serve, dispatch)
        else:
            one_chip(serve, dispatch)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s; compile "
        f"cache now {entries} entries")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
