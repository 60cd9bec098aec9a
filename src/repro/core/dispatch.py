"""Unified attention dispatch — the single seam every attention-bearing
model targets (DESIGN.md §8).

``attention_dispatch(q, k, v, grid=..., cfg=..., ...)`` owns, in order:

  1. **Policy resolution** — which sparsity *strategy* decides the
     masks/snaps: a registered :class:`~repro.core.policy.ReusePolicy`
     ('ripple', 'svg', 'equal_mse', 'dense', or anything registered
     out-of-tree), resolved from ``cfg.policy`` / the explicit
     ``policy`` argument (DESIGN.md §11).  The policy produces one
     :class:`~repro.core.policy.ReuseDecision`; dispatch executes it.
  2. **Backend selection** — dense SDPA, the dense snapped reference,
     the exact pair-collapse math, the block-skipping Pallas ripple
     kernel, or the block-sparse masked flash kernel
     (``kernels/sparse``, DESIGN.md §12) for policies that tile their
     masks into a skip/full/partial block map; resolved from
     ``cfg.backend`` / the explicit ``backend`` argument, the platform,
     the policy's needs, and shape eligibility.
  3. **Mask pipeline placement** — the Fig. 6 step ①-② Δ-checks run
     either fused on-device (``kernels/reuse_mask``) or on the host
     (``core.reuse``), per ``cfg.fused_mask`` and grid eligibility.
  4. **Shape bucketing** — plan lookups key on power-of-two shape
     buckets, so nearby workload shapes share one resolved plan and the
     jit cache does not fragment per exact token count.
  5. **Block-size autotuning** — per (shape-bucket, backend) block sizes
     for the Pallas kernel come from a persistent on-disk cache
     (``REPRO_AUTOTUNE_CACHE``), populated offline by
     :func:`autotune_attention` (benchmarks/kernel_bench.py sweeps it);
     plan resolution never times kernels inside a trace.

Model code calls :func:`attention_dispatch` via
``models.attention.mha_attention``.

When a mesh is active (:func:`dispatch_mesh` / :func:`set_dispatch_mesh`
— the serving launchers install one), plan resolution additionally
records **batch/head sharding**: the leading batch dim shards over the
(pod, data) axes and the heads dim over 'model' whenever they divide,
and the whole pipeline — Δ-check mask computation included — runs under
``shard_map`` with the mask computed *per shard*.  The reuse windows run
along the t/x/y token axes, never along batch or heads, so the halo for
the sharded axes is exactly zero and per-shard results are bitwise equal
to the single-device path (DESIGN.md §10).  Indivisible shapes fall back
to replicated execution with the same plan cache entry semantics.

A mesh with a third ``seq`` axis additionally shards the **token axis**
— context-parallel ring attention with an explicit ``window − 1`` frame
halo for the Δ-checks and per-hop block-map elision (``core.ring``,
DESIGN.md §14) — for policies that declare ``will_seq_shard`` and
shapes where the grid covers the whole sequence and T divides by the
seq degree.  Everything else falls back to the replicated token axis,
never an error.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import time
import warnings
from collections import OrderedDict
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.config.base import RippleConfig
from repro.core.collapse import collapsed_attention
from repro.core.policy import (ReuseDecision, ReusePolicy, RippleStats,
                               get_policy, list_policies, register_policy)
from repro.core.savings import collapse_savings
from repro.utils.platform import platform

__all__ = [
    "attention_dispatch", "autotune_attention", "DispatchPlan",
    "RippleStats", "ReuseDecision", "ReusePolicy", "dense_attention",
    "dispatch_mesh", "get_policy", "list_policies", "plan_for_shape",
    "register_policy", "resolve_backend", "resolve_plan",
    "set_dispatch_mesh", "shape_bucket",
]

BACKENDS = ("auto", "dense", "reference", "collapse", "pallas", "sparse")
_DEFAULT_BLOCKS = (128, 128)
# (block_q, block_k) candidates the autotuner sweeps; the ops-level
# wrappers pad to block multiples so every candidate is shape-legal.
BLOCK_CANDIDATES = ((64, 64), (128, 128), (128, 256), (256, 128),
                    (256, 256))


@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Resolved execution plan for one (policy, shape-bucket, backend)
    cell.  ``policy`` is the resolved reuse-policy *name* (the object is
    looked up at execution time so re-registration takes effect); the
    plan/LRU caches and the shard_map path key on it."""

    backend: str  # 'dense' | 'reference' | 'collapse' | 'pallas' | 'sparse'
    policy: str = "ripple"
    block_q: int = 128
    block_k: int = 128
    fused_mask: bool = False
    bucket: Tuple[int, ...] = ()
    tuned: bool = False   # block sizes came from the autotune cache
    # Mesh sharding (DESIGN.md §10): which mesh axes shard the leading
    # batch dim / the heads dim; () / None means replicated execution.
    batch_axes: Tuple[str, ...] = ()
    head_axis: Optional[str] = None
    batch_shards: int = 1
    head_shards: int = 1
    # Context-parallel ring attention (DESIGN.md §14): the mesh axis
    # sharding the token axis, None when the tokens stay replicated.
    seq_axis: Optional[str] = None
    seq_shards: int = 1

    @property
    def sharded(self) -> bool:
        return self.batch_shards * self.head_shards * self.seq_shards > 1

    def summary(self) -> str:
        blk = (f" block={self.block_q}x{self.block_k}"
               f"{' (tuned)' if self.tuned else ''}"
               if self.backend in ("pallas", "sparse") else "")
        mask = " fused-mask" if self.fused_mask else ""
        shard = (f" shard=batch{self.batch_shards}x"
                 f"heads{self.head_shards}" if self.sharded else "")
        ring = (f" ring=seq{self.seq_shards}" if self.seq_axis else "")
        return (f"attention[{self.policy}/{self.backend}{blk}{mask}{shard}"
                f"{ring} bucket={self.bucket}]")


def dense_attention(q, k, v, scale, bias=None):
    """Plain SDPA; the 'dense' backend and the inactive-config path."""
    logits = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...qk,...kv->...qv", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Shape bucketing
# ---------------------------------------------------------------------------


def shape_bucket(n: int) -> int:
    """Round up to the next power of two (min 64) — plan-cache bucket."""
    return max(64, 1 << max(0, math.ceil(math.log2(max(n, 1)))))


def _bucket_key(q_shape, v_shape, backend: str) -> Tuple:
    *lead, n, d = q_shape
    bh = math.prod(lead) if lead else 1
    return (backend, shape_bucket(bh), shape_bucket(n), d, v_shape[-1])


# ---------------------------------------------------------------------------
# Active mesh (installed by launchers/engines; consulted by plan resolution)
# ---------------------------------------------------------------------------

_ACTIVE_MESH: Optional[Mesh] = None


def set_dispatch_mesh(mesh: Optional[Mesh]) -> Optional[Mesh]:
    """Install ``mesh`` as the dispatch-layer mesh; returns the previous
    one.  ``None`` restores single-device (replicated) execution."""
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    return prev


def active_dispatch_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH


@contextlib.contextmanager
def dispatch_mesh(mesh: Optional[Mesh]):
    """Scoped :func:`set_dispatch_mesh` (tests, benchmarks)."""
    prev = set_dispatch_mesh(mesh)
    try:
        yield mesh
    finally:
        set_dispatch_mesh(prev)


def _mesh_key(mesh: Optional[Mesh]) -> Optional[Tuple]:
    if mesh is None:
        return None
    return tuple((a, int(mesh.shape[a])) for a in mesh.axis_names)


def _resolve_sharding(mesh: Optional[Mesh], q_shape) -> Tuple:
    """(batch_axes, head_axis, batch_shards, head_shards) for q_shape.

    Greedy prefix of the (pod, data) axes that divides the leading batch
    dim; heads (dim 1 of a 4-D operand) shard over 'model' when they
    divide.  Anything indivisible stays replicated — never an error.
    """
    if mesh is None or len(q_shape) < 3:
        return (), None, 1, 1
    b_axes = []
    b_shards = 1
    B = q_shape[0]
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n = int(mesh.shape[a])
            if n > 1 and B % (b_shards * n) == 0:
                b_axes.append(a)
                b_shards *= n
    head_axis, h_shards = None, 1
    if len(q_shape) >= 4 and "model" in mesh.axis_names:
        n = int(mesh.shape["model"])
        if n > 1 and q_shape[1] % n == 0:
            head_axis, h_shards = "model", n
    return tuple(b_axes), head_axis, b_shards, h_shards


# ---------------------------------------------------------------------------
# Persistent autotune cache
# ---------------------------------------------------------------------------

_DISK_CACHE: Optional[Dict[str, dict]] = None
_DISK_CACHE_PATH: Optional[str] = None
# Bounded LRU: resolve_plan moves hits to the MRU end and evicts from the
# LRU end past the cap, so the hottest plans always survive eviction.
_PLAN_CACHE: "OrderedDict[Tuple, DispatchPlan]" = OrderedDict()
_PLAN_CACHE_CAP = int(os.environ.get("REPRO_PLAN_CACHE_CAP", "256"))


def autotune_cache_path() -> str:
    return os.environ.get(
        "REPRO_AUTOTUNE_CACHE",
        os.path.join(os.path.expanduser("~"), ".cache", "repro_timeripple",
                     "autotune.json"))


def clear_plan_cache():
    """Drop the in-memory caches (tests; after switching cache files)."""
    global _DISK_CACHE, _DISK_CACHE_PATH
    _DISK_CACHE = None
    _DISK_CACHE_PATH = None
    _PLAN_CACHE.clear()


# Versioned schema marker written into the autotune cache file.  Files
# without the marker are accepted as legacy; a *mismatched* marker (or
# corrupt/truncated JSON, or entries missing the block fields) warns
# and regenerates instead of raising — a bad cache file must never
# take down a launcher.
_AUTOTUNE_SCHEMA = "repro-autotune/1"


def _read_disk_cache(p: str) -> Dict[str, dict]:
    try:
        with open(p) as f:
            raw = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        warnings.warn(f"autotune cache {p!r} is corrupt ({e}); "
                      f"regenerating it", RuntimeWarning, stacklevel=3)
        return {}
    if not isinstance(raw, dict):
        warnings.warn(f"autotune cache {p!r} is not a JSON object; "
                      f"regenerating it", RuntimeWarning, stacklevel=3)
        return {}
    schema = raw.pop("__schema__", _AUTOTUNE_SCHEMA)
    if schema != _AUTOTUNE_SCHEMA:
        warnings.warn(f"autotune cache {p!r} has schema {schema!r} != "
                      f"{_AUTOTUNE_SCHEMA!r}; regenerating it",
                      RuntimeWarning, stacklevel=3)
        return {}
    bad = [k for k, v in raw.items()
           if not (isinstance(v, dict) and "block_q" in v
                   and "block_k" in v)]
    if bad:
        warnings.warn(f"autotune cache {p!r}: dropping malformed "
                      f"entries {bad}", RuntimeWarning, stacklevel=3)
    return {k: v for k, v in raw.items() if k not in bad}


def _load_disk_cache(path: Optional[str] = None) -> Dict[str, dict]:
    global _DISK_CACHE, _DISK_CACHE_PATH
    p = path or autotune_cache_path()
    if _DISK_CACHE is None or p != _DISK_CACHE_PATH:
        _DISK_CACHE = _read_disk_cache(p)
        _DISK_CACHE_PATH = p
    return _DISK_CACHE


def _store_disk(key: str, entry: dict, path: Optional[str] = None):
    p = path or autotune_cache_path()
    cache = _load_disk_cache(path)
    cache[key] = entry
    from repro.utils.diskio import atomic_write_text

    atomic_write_text(p, json.dumps(
        {"__schema__": _AUTOTUNE_SCHEMA, **cache}, indent=1, sort_keys=True))


def autotune_key(backend: str, n_bucket: int, d: int, dv: int) -> str:
    # Keyed by platform: block sizes tuned on a CPU interpret run must
    # never steer the TPU kernel (and vice versa).
    return f"{platform()}:{backend}:n{n_bucket}:d{d}:dv{dv}"


def autotune_attention(q, k, v, *, backend: str = "pallas",
                       candidates: Sequence[Tuple[int, int]] = BLOCK_CANDIDATES,
                       repeats: int = 3, cache_path: Optional[str] = None,
                       force: bool = False,
                       interpret: Optional[bool] = None) -> dict:
    """Time each (block_q, block_k) candidate on *concrete* operands and
    persist the winner keyed by the shape bucket.

    Runs outside any trace (benchmarks, warm-up scripts) — never call it
    from jitted model code; :func:`attention_dispatch` only *reads* the
    cache it writes.  Returns the winning cache entry.

    ``backend`` picks the kernel being tuned: 'pallas' (the ripple
    pair-collapse kernel) or 'sparse' (the block-sparse masked flash
    kernel, timed on an all-full map — the dense-tile inner loop is
    what the block sizes shape; skip tiles cost nothing regardless).
    """
    if backend == "sparse":
        from repro.kernels.sparse.ops import sparse_attention_pallas

        def make(bq, bk):
            return lambda: sparse_attention_pallas(
                q, k, v, block_q=bq, block_k=bk, interpret=interpret)
    else:
        from repro.kernels.ripple.ops import ripple_attention_pallas

        def make(bq, bk):
            return lambda: ripple_attention_pallas(
                q, k, v, block_q=bq, block_k=bk, interpret=interpret)

    key = autotune_key(backend, shape_bucket(q.shape[-2]), q.shape[-1],
                       v.shape[-1])
    cache = _load_disk_cache(cache_path)
    if key in cache and not force:
        return cache[key]

    results = []
    for bq, bk in candidates:
        results.append({"block_q": bq, "block_k": bk,
                        "us": round(time_best(make(bq, bk), repeats) * 1e6,
                                    1)})
    best = min(results, key=lambda r: r["us"])
    entry = {**best, "device": platform(), "candidates": results}
    _store_disk(key, entry, cache_path)
    _PLAN_CACHE.clear()  # plans may now resolve to the tuned blocks
    return entry


def time_best(fn, repeats: int = 3) -> float:
    """Compile-and-warm once, then min-of-``repeats`` walltime in
    seconds — the one timing idiom shared by the autotuner and the
    kernel benchmarks."""
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _tuned_blocks(backend: str, n: int, d: int, dv: int):
    entry = _load_disk_cache().get(autotune_key(backend, shape_bucket(n),
                                                d, dv))
    if entry:
        return int(entry["block_q"]), int(entry["block_k"]), True
    return (*_DEFAULT_BLOCKS, False)


# ---------------------------------------------------------------------------
# Plan resolution
# ---------------------------------------------------------------------------


def resolve_backend(cfg: RippleConfig, backend: Optional[str], *,
                    has_bias: bool, n_tokens: int,
                    policy: Optional[ReusePolicy] = None) -> str:
    """Collapse 'auto' onto a concrete backend for this call.

    The policy's declared needs gate the choice without the dispatcher
    knowing the strategy: bias-emitting policies avoid the biasless
    auto-Pallas path, non-snapping policies gain nothing from collapse.
    """
    pol = policy if policy is not None else get_policy(cfg.policy)
    b = backend or cfg.backend or "auto"
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r}; expected one of {BACKENDS}")
    if not cfg.active() or pol.is_dense:
        return "dense"
    emits_bias = pol.will_emit_bias(cfg)
    # The block-sparse backend realizes a policy's mask as skipped
    # tiles, but only when the policy's own bias is the whole story: an
    # external caller bias is dense/arbitrary, and the sparse kernel's
    # full-tile fast path would silently drop it.
    sparse_ok = pol.will_emit_block_map(cfg) and not has_bias
    if b != "auto":
        # A policy-emitted bias rules out backends that can't carry it:
        # the Pallas kernel asserts bias is None, and collapse assumes a
        # window-constant bias (an SVG block mask isn't).  Downgrade the
        # explicit choice to the block-sparse kernel when the policy can
        # tile its mask, else the reference path — never crash inside a
        # jitted sampler, same fall-back-not-error stance as sharding.
        if emits_bias and b in ("pallas", "collapse"):
            return "sparse" if sparse_ok else "reference"
        if b == "sparse" and has_bias and pol.will_emit_block_map(cfg):
            # A map-emitting policy derives FULL tiles from its own keep
            # mask; the kernel's full-tile fast path would then drop the
            # external caller bias.  Same downgrade stance as above.
            return "reference"
        return b
    if sparse_ok:
        # On TPU the sparse kernel skips masked tiles' MXU work; on CPU
        # it runs in interpret mode (correctness-representative, same
        # stance as the other kernels) so mask policies never silently
        # lose their structural savings to a dense fallback.
        return "sparse"
    pallas_ok = (platform() == "tpu" and not has_bias and not emits_bias
                 and cfg.window == 2 and n_tokens % 2 == 0)
    if pallas_ok:
        return "pallas"
    if not pol.snaps_operands or emits_bias:
        return "reference"
    return "collapse" if cfg.execution == "collapse" else "reference"


def _fused_requested(cfg: RippleConfig) -> bool:
    if cfg.fused_mask == "on":
        return True
    if cfg.fused_mask == "off":
        return False
    # 'auto': the fused kernel wins on TPU; in interpret mode on CPU it
    # is correctness-representative but slower than the fused-by-XLA
    # host path, so it stays off there.
    return platform() == "tpu"


def _resolve_seq_sharding(mesh: Optional[Mesh], q_shape, resolved: str,
                          cfg: RippleConfig, pol: ReusePolicy,
                          grid, grid_slice) -> Tuple[Optional[str], int]:
    """(seq_axis, seq_shards): is the context-parallel ring eligible
    (DESIGN.md §14)?  Needs a >1 'seq' mesh axis, a ring-capable backend
    (reference or sparse), 4-D operands, a grid covering the whole
    sequence (no text prefix — ``grid_slice`` must be None after the
    dispatcher's full-range normalization), a policy that declares
    ``will_seq_shard``, and T divisible by the seq degree.  Anything
    else replicates the token axis — fall back, never error."""
    if (mesh is None or "seq" not in mesh.axis_names or grid is None
            or grid_slice is not None or len(q_shape) < 4
            or resolved not in ("reference", "sparse")
            or not pol.will_seq_shard(cfg)):
        return None, 1
    s = int(mesh.shape["seq"])
    T = int(grid[0])
    n = math.prod(int(g) for g in grid)
    if s <= 1 or n != q_shape[-2] or T % s != 0:
        return None, 1
    return "seq", s


def resolve_plan(q_shape, v_shape, cfg: RippleConfig,
                 backend: Optional[str] = None,
                 has_bias: bool = False,
                 mesh: Optional[Mesh] = None,
                 policy=None,
                 grid: Optional[Tuple[int, int, int]] = None,
                 grid_slice: Optional[Tuple[int, int]] = None
                 ) -> DispatchPlan:
    """Shape-bucketed, cached plan resolution (trace-safe: shapes only).

    ``mesh`` defaults to the active dispatch mesh; when one is present
    the cache keys on the *exact* leading dims (sharding eligibility is
    a divisibility property, not a bucket property) plus the mesh shape.
    ``policy`` (a registered name or ReusePolicy) defaults to
    ``cfg.policy``; the cache keys on the policy name.  ``grid`` /
    ``grid_slice`` feed seq-axis (ring) eligibility — callers that only
    know shapes simply never get a ring plan.
    """
    if mesh is None:
        mesh = _ACTIVE_MESH
    if grid_slice is not None and grid is not None \
            and tuple(grid_slice) == (0, q_shape[-2]):
        grid_slice = None  # full-range slice is no slice at all
    pol = get_policy(policy if policy is not None else cfg.policy)
    n = q_shape[-2]
    resolved = resolve_backend(cfg, backend, has_bias=has_bias, n_tokens=n,
                               policy=pol)
    # plan_token mixes in external decision state the policy bakes into
    # compiled constants (the pattern artifact's content hash) so an
    # artifact swap can never replay a stale plan; getattr keeps
    # pre-token duck-typed policies working.
    tok = getattr(pol, "plan_token", None)
    key = _bucket_key(q_shape, v_shape, resolved) \
        + (pol.name, cfg.fused_mask, cfg.window, cfg.granularity,
           tok(cfg) if callable(tok) else None)
    if mesh is not None:
        key = key + (_mesh_key(mesh), tuple(q_shape[:-2]),
                     grid, grid_slice is None)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _PLAN_CACHE.move_to_end(key)
        return plan
    if resolved in ("pallas", "sparse"):
        bq, bk, tuned = _tuned_blocks(resolved, n, q_shape[-1], v_shape[-1])
    else:
        (bq, bk), tuned = _DEFAULT_BLOCKS, False
    b_axes, h_axis, b_shards, h_shards = (
        _resolve_sharding(mesh, q_shape) if resolved != "dense"
        else ((), None, 1, 1))
    seq_axis, seq_shards = _resolve_seq_sharding(
        mesh, q_shape, resolved, cfg, pol, grid, grid_slice)
    plan = DispatchPlan(backend=resolved, policy=pol.name, block_q=bq,
                        block_k=bk, fused_mask=_fused_requested(cfg),
                        bucket=key[1:3], tuned=tuned,
                        batch_axes=b_axes, head_axis=h_axis,
                        batch_shards=b_shards, head_shards=h_shards,
                        seq_axis=seq_axis, seq_shards=seq_shards)
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > _PLAN_CACHE_CAP:
        _PLAN_CACHE.popitem(last=False)
    return plan


def plan_for_shape(n_tokens: int, head_dim: int, cfg: RippleConfig, *,
                   batch_heads: int = 1, heads: int = 0,
                   backend: Optional[str] = None,
                   mesh: Optional[Mesh] = None,
                   policy=None) -> DispatchPlan:
    """Plan metadata for launchers/engines that only know shapes.

    ``heads`` (when it divides ``batch_heads``) splits the flattened
    leading dim into (batch, heads) so mesh head-sharding is visible in
    the returned plan.
    """
    if heads and batch_heads % heads == 0:
        shape = (batch_heads // heads, heads, n_tokens, head_dim)
    else:
        shape = (batch_heads, n_tokens, head_dim)
    return resolve_plan(shape, shape, cfg, backend=backend, mesh=mesh,
                        policy=policy)


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------


def _decide_extra(plan: DispatchPlan, policy: ReusePolicy,
                  cfg: RippleConfig) -> dict:
    extra = {}
    if plan.backend == "sparse" and policy.will_emit_block_map(cfg):
        # Only sparse plans for map-emitting policies pass block_shape:
        # policies predating the block-sparse backend keep their
        # original decide() signature even under a forced 'sparse'
        # (their mapless decision runs the kernel's all-full path).
        extra["block_shape"] = (plan.block_q, plan.block_k)
    return extra


def _execute_backend(d: ReuseDecision, v, scale, *, plan: DispatchPlan,
                     cfg: RippleConfig):
    """Fig. 6 steps ③-④: run the planned backend on one decision.
    Returns ``out``, or ``(out, skipped)`` from a backend that reports
    the share of MXU work it skipped itself: the ripple kernel, from the
    block flags it ran on (deriving them again would read q and k a
    second time).  :func:`_backend_skipped` covers the others."""
    if plan.backend == "pallas":
        # Deferred import: kernels are optional at module-import time.
        from repro.kernels.ripple.ops import ripple_attention_pallas

        return ripple_attention_pallas(d.q, d.k, v, bias=d.bias,
                                       window=cfg.window,
                                       block_q=plan.block_q,
                                       block_k=plan.block_k,
                                       return_skipped=True)
    if plan.backend == "sparse":
        from repro.kernels.sparse.ops import sparse_attention_pallas

        return sparse_attention_pallas(d.q, d.k, v, bias=d.bias,
                                       block_map=d.block_map,
                                       block_q=plan.block_q,
                                       block_k=plan.block_k)
    if plan.backend == "collapse":
        return collapsed_attention(d.q, d.k, v, bias=d.bias,
                                   window=cfg.window, scale=scale)
    # 'reference': dense attention on the decided operands
    return dense_attention(d.q, d.k, v, scale, d.bias)


def _run_backend(d: ReuseDecision, v, scale, *, plan: DispatchPlan,
                 cfg: RippleConfig):
    """:func:`_execute_backend`, as ``(out, skipped)``."""
    res = _execute_backend(d, v, scale, plan=plan, cfg=cfg)
    if isinstance(res, tuple):
        return res
    return res, _backend_skipped(d, plan)


def _backend_skipped(d: ReuseDecision, plan: DispatchPlan):
    """The share of attention MXU work a backend that does not report it
    skips on this decision: the sparse kernel's skipped tiles, the
    collapse path's collapsed windows, and nothing for dense execution
    on the decided operands."""
    zero = jnp.zeros((), jnp.float32)
    if plan.backend == "sparse":
        from repro.kernels.sparse.ops import sparse_block_stats

        return zero if d.block_map is None \
            else sparse_block_stats(d.block_map)
    if plan.backend == "collapse" and d.q_mask is not None \
            and d.k_mask is not None:
        return collapse_savings(d.q_mask, d.k_mask, d.window)
    return zero


def _call_stats(policy: ReusePolicy, d: ReuseDecision,
                skipped) -> RippleStats:
    """The policy's accounting of one decision, with the structural
    savings the executed backend reported."""
    return dataclasses.replace(policy.stats(d), structural_savings=skipped)


def _shard_mean(stats: RippleStats, mesh: Mesh) -> RippleStats:
    """Inside shard_map: the mean of every shard's stats — one ``psum``
    of the four per-shard values and a shard count.  Shards are equal
    slices of (batch, heads), so this is the unsharded mean."""
    vec = jnp.stack([jnp.asarray(x, jnp.float32) for x in (
        stats.savings, stats.structural_savings, stats.q_snap_frac,
        stats.k_snap_frac, 1.0)])
    vec = jax.lax.psum(vec, tuple(mesh.axis_names))
    return RippleStats(*(vec[i] / vec[4] for i in range(4)))


def _inject_attn_nan(out, step):
    """Chaos-harness hook (``serving.faults``, DESIGN.md §17.3): when an
    ``attn_nan`` fault is armed at trace time, flip this call's output
    to NaN at the spec'd denoising step.  Only the sparse pipelines call
    this — the dispatcher's dense path never does — so a degraded
    bucket's dense recompile clears the fault, the way a real
    sparse-kernel bug would."""
    if step is None:
        return out
    from repro.serving import faults as fault_lib

    fault = fault_lib.active_faults()
    spec = fault.spec("attn_nan") if fault is not None else None
    if spec is None:
        return out
    fault.note_fired("attn_nan")
    at = jnp.asarray(int(spec.param("step", 0)), jnp.int32)
    return jnp.where(jnp.equal(jnp.asarray(step, jnp.int32), at),
                     jnp.full_like(out, jnp.nan), out)


def _run_pipeline(q, k, v, thetas, scale, bias, *, plan: DispatchPlan,
                  grid, cfg: RippleConfig, grid_slice,
                  policy: ReusePolicy):
    """Fig. 6 steps ①-④ for one resolved plan: the policy's decision
    (snap / mask), then the planned backend on it.  Returns
    (out, RippleStats).  Shard-oblivious: runs identically on the full
    operands or on one shard_map shard (decisions only look along t/x/y,
    DESIGN.md §10).
    """
    d = policy.decide(q, k, grid=grid, cfg=cfg, thetas=thetas, bias=bias,
                      grid_slice=grid_slice, fused=plan.fused_mask,
                      **_decide_extra(plan, policy, cfg))
    out, skipped = _run_backend(d, v, scale, plan=plan, cfg=cfg)
    return out, _call_stats(policy, d, skipped)


def _run_pipeline_cached(q, k, v, thetas, scale, *, plan: DispatchPlan,
                         grid, cfg: RippleConfig, grid_slice,
                         policy: ReusePolicy, step, cached,
                         total_steps=None):
    """The cross-step decision-cache pipeline (DESIGN.md §13): decide
    fresh when the cadence / drift guard says the cached plan is stale,
    otherwise re-apply the carried plan to the fresh operands — both
    arms of one ``lax.cond`` producing structurally identical
    (ReuseDecision, CachedDecision) pairs, so the state is
    scan-carriable.  The backend then executes once on the selected
    decision (the kernels are not duplicated into the branches).
    External bias must be None (the dispatcher gates this).  Returns
    (out, RippleStats, new_cache).
    """
    from repro.core import decision_cache as dc

    extra = _decide_extra(plan, policy, cfg)
    plan_once = getattr(policy, "plan_once", False)
    # The drift statistic is only worth its O(N·c) pass when the guard
    # can act on it; with the guard off — or for plan-once policies,
    # whose decision is a trajectory constant — the carry keeps a zero
    # stat so the pytree structure (and cadence behaviour) is identical.
    if cfg.drift_tol > 0 and not plan_once:
        stat = dc.drift_stat(q, k, cfg)
    else:
        stat = jnp.zeros(q.shape[:-2], jnp.float32)

    def fresh(prev):
        d = policy.decide(q, k, grid=grid, cfg=cfg, thetas=thetas,
                          bias=None, grid_slice=grid_slice,
                          fused=plan.fused_mask, want_plan=True, **extra)
        return d, dc.cache_from_decision(d, stat, prev=prev)

    if cached is None:
        d, new_cache = fresh(None)
    else:
        def reuse(prev):
            d = policy.apply_decision(q, k, prev, grid=grid, cfg=cfg,
                                      thetas=thetas, grid_slice=grid_slice)
            return d, dc.bump_hit(prev)

        if plan_once:
            # Refresh cadence of never: the step-0 plan is replayed for
            # the whole trajectory (no reuse_every, no drift, no
            # final-step re-decide) — DESIGN.md §16.
            refresh = jnp.equal(jnp.asarray(step, jnp.int32), 0)
        else:
            refresh = dc.refresh_due(step, cfg, stat, cached.ref_stat,
                                     total_steps)
        d, new_cache = jax.lax.cond(refresh, fresh, reuse, cached)
    out, skipped = _run_backend(d, v, scale, plan=plan, cfg=cfg)
    out = _inject_attn_nan(out, step)
    if cfg.sentinel:
        from repro.core import guardrail

        # Sentinel readings ride the cache carry (DESIGN.md §17): the
        # probe compares against the *original* q/k, not the snapped
        # operands — it measures the full approximation error.
        new_cache = guardrail.attach_sentinel(new_cache, out, q, k, v,
                                              scale, step, cfg)
    return out, _call_stats(policy, d, skipped), new_cache


def _operand_spec(plan: DispatchPlan, ndim: int) -> P:
    """PartitionSpec for a (..., N, d) attention operand under ``plan``."""
    entries: list = [None] * ndim
    if plan.batch_axes:
        entries[0] = (plan.batch_axes if len(plan.batch_axes) > 1
                      else plan.batch_axes[0])
    if plan.head_axis is not None and ndim >= 4:
        entries[1] = plan.head_axis
    if plan.seq_axis is not None and ndim >= 3:
        entries[ndim - 2] = plan.seq_axis
    return P(*entries)


def _lead_spec(plan: DispatchPlan, ndim: int) -> P:
    """PartitionSpec for a decision-cache leaf: every leaf keeps the
    operands' leading (batch, head) dims (DESIGN.md §13), whatever its
    trailing rank — snap-source maps (..., Ng, d), biases (..., N, N),
    block maps (..., nq, nk), and lead-shaped stats/counters alike.
    ``plan.head_axis`` is only ever set for 4-D operands, so placing it
    at dim 1 is always correct here."""
    entries: list = [None] * ndim
    if plan.batch_axes and ndim >= 1:
        entries[0] = (plan.batch_axes if len(plan.batch_axes) > 1
                      else plan.batch_axes[0])
    if plan.head_axis is not None and ndim >= 2:
        entries[1] = plan.head_axis
    return P(*entries)


def _sharded_pipeline(q, k, v, thetas, scale, *, plan: DispatchPlan,
                      mesh: Mesh, grid, cfg: RippleConfig, grid_slice,
                      policy: ReusePolicy, step=None, cached=None,
                      want_cache: bool = False, total_steps=None,
                      with_stats: bool = False):
    """Run :func:`_run_pipeline` under shard_map over the plan's batch /
    head axes.  No collectives: the sharded axes never carry a reuse
    window (the policy contract — decisions look only along t/x/y), so
    each shard's decision is self-contained (zero halo) and the result
    is bitwise-identical to the replicated path.

    With ``want_cache`` the decision cache rides along: every cache
    leaf keeps the operands' leading dims, so each shard carries (and
    refreshes) exactly its own cache slice — drift on one shard
    refreshes that shard alone.  Returns (out, new_cache) then.

    With ``with_stats`` each shard's :class:`RippleStats` are combined
    by one ``psum`` (:func:`_shard_mean`) and appended to the return.
    The ring path carries no counters: its stats are NaN.
    """
    from jax.experimental.shard_map import shard_map

    spec = _operand_spec(plan, q.ndim)
    th_vec = jnp.stack([jnp.asarray(thetas[a], jnp.float32)
                        for a in ("t", "x", "y")])
    scale = jnp.asarray(scale, jnp.float32)
    stat_spec = (RippleStats(P(), P(), P(), P()),) if with_stats else ()

    def result(out, stats, *cache):
        res = (out, *cache) + ((_shard_mean(stats, mesh),)
                               if with_stats else ())
        return res if len(res) > 1 else out

    if plan.seq_axis is not None:
        # Context-parallel ring attention (core.ring, DESIGN.md §14).
        # Deferred import: ring lazily imports dense_attention back.
        from repro.core import ring as ring_lib

        nan = jnp.full((), jnp.nan, jnp.float32)
        no_stats = ((RippleStats(nan, nan, nan, nan),) if with_stats
                    else ())
        if not want_cache:
            def ring_body(qs, ks, vs, th, sc):
                th_d = {"t": th[0], "x": th[1], "y": th[2]}
                return ring_lib.ring_pipeline(
                    qs, ks, vs, th_d, sc, plan=plan, grid=grid, cfg=cfg,
                    policy=policy)

            fn = shard_map(ring_body, mesh=mesh,
                           in_specs=(spec, spec, spec, P(), P()),
                           out_specs=spec, check_rep=False)
            out = fn(q, k, v, th_vec, scale)
            return (out, *no_stats) if with_stats else out

        rstep = jnp.asarray(step, jnp.int32)
        # Deterministic spec construction — no eval_shape: the ring body
        # contains collectives, which only abstract-eval inside
        # shard_map, and the leaf structure is fixed by (plan, cfg).
        cache_specs = ring_lib.ring_cache_specs(plan, cfg)

        def ring_cached(qs, ks, vs, th, sc, st, *cache):
            th_d = {"t": th[0], "x": th[1], "y": th[2]}
            return ring_lib.ring_pipeline(
                qs, ks, vs, th_d, sc, plan=plan, grid=grid, cfg=cfg,
                policy=policy, step=st,
                cached=cache[0] if cache else None, want_cache=True,
                total_steps=total_steps)

        in_specs = (spec, spec, spec, P(), P(), P()) + (
            (cache_specs,) if cached is not None else ())
        fn = shard_map(ring_cached, mesh=mesh, in_specs=in_specs,
                       out_specs=(spec, cache_specs), check_rep=False)
        args = (q, k, v, th_vec, scale, rstep) + (
            (cached,) if cached is not None else ())
        return fn(*args) + no_stats

    if not want_cache:
        def body(qs, ks, vs, th, sc):
            th_d = {"t": th[0], "x": th[1], "y": th[2]}
            out, stats = _run_pipeline(qs, ks, vs, th_d, sc, None,
                                       plan=plan, grid=grid, cfg=cfg,
                                       grid_slice=grid_slice,
                                       policy=policy)
            return result(out, stats)

        fn = shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, spec, P(), P()),
                       out_specs=(spec, *stat_spec) if with_stats else spec,
                       check_rep=False)
        return fn(q, k, v, th_vec, scale)

    step = jnp.asarray(step, jnp.int32)
    # The cache's pytree structure (for the out_specs) without running
    # anything: abstract-eval the cached pipeline.  Identical to the
    # runtime structure by construction — it is the same call.
    tmpl = cached if cached is not None else jax.eval_shape(
        lambda qq, kk, vv, st: _run_pipeline_cached(
            qq, kk, vv, thetas, scale, plan=plan, grid=grid, cfg=cfg,
            grid_slice=grid_slice, policy=policy, step=st, cached=None,
            total_steps=total_steps)[2],
        q, k, v, step)
    cache_specs = jax.tree_util.tree_map(
        lambda a: _lead_spec(plan, len(a.shape)), tmpl)

    def body(qs, ks, vs, th, sc, st, *cache):
        th_d = {"t": th[0], "x": th[1], "y": th[2]}
        out, stats, new_cache = _run_pipeline_cached(
            qs, ks, vs, th_d, sc, plan=plan, grid=grid, cfg=cfg,
            grid_slice=grid_slice, policy=policy, step=st,
            cached=cache[0] if cache else None, total_steps=total_steps)
        return result(out, stats, new_cache)

    in_specs = (spec, spec, spec, P(), P(), P()) + (
        (cache_specs,) if cached is not None else ())
    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=(spec, cache_specs, *stat_spec),
                   check_rep=False)
    args = (q, k, v, th_vec, scale, step) + (
        (cached,) if cached is not None else ())
    return fn(*args)


def attention_dispatch(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    grid: Tuple[int, int, int],
    cfg: RippleConfig,
    step: Optional[jax.Array] = None,
    total_steps: Optional[int] = None,
    thetas: Optional[Dict[str, jax.Array]] = None,
    bias: Optional[jax.Array] = None,
    grid_slice: Optional[Tuple[int, int]] = None,
    backend: Optional[str] = None,
    mesh: Optional[Mesh] = None,
    policy=None,
    with_stats: bool = False,
    cached_decision=None,
    return_decision: bool = False,
):
    """Sparse attention behind one dispatch seam.

    q, k, v: (..., N, head_dim), post-RoPE.  ``policy`` (a registered
    name or ReusePolicy) overrides ``cfg.policy`` — it chooses the
    sparsity *strategy* (DESIGN.md §11); ``backend`` overrides
    ``cfg.backend`` for this call ('dense' bypasses the reuse pipeline
    entirely — e.g. cross-attention).  ``thetas`` overrides the policy's
    per-step schedule (otherwise derived from ``step``/``total_steps``).
    ``mesh`` overrides the active dispatch mesh; when the resolved plan
    carries sharding, the pipeline runs under shard_map (DESIGN.md §10).

    Cross-step decision cache (DESIGN.md §13): ``cached_decision`` is a
    :class:`~repro.core.decision_cache.CachedDecision` from an earlier
    call on identically-shaped operands — the decision is then only
    recomputed when ``step % cfg.reuse_every == 0`` or the drift guard
    fires, and otherwise cheaply re-applied to the fresh operands.
    ``return_decision=True`` (implied by passing ``cached_decision``)
    returns the updated cache as the second element so samplers can
    carry it through their scan.  Requires an active cache-capable
    policy, a concrete ``step``, and no external ``bias``.

    Returns ``out``, ``(out, RippleStats)``, ``(out, CachedDecision)``
    or ``(out, CachedDecision, RippleStats)``.
    """
    if mesh is None:
        mesh = _ACTIVE_MESH
    if grid_slice is not None and tuple(grid_slice) == (0, q.shape[-2]):
        grid_slice = None  # full-range slice: the whole sequence is grid
    pol = get_policy(policy if policy is not None else cfg.policy)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    plan = resolve_plan(q.shape, v.shape, cfg, backend=backend,
                        has_bias=bias is not None, mesh=mesh, policy=pol,
                        grid=grid, grid_slice=grid_slice)
    want_cache = return_decision or cached_decision is not None
    if want_cache:
        if plan.backend == "dense" or not pol.will_cache_decisions(cfg):
            raise ValueError(
                f"decision caching requested but policy {pol.name!r} "
                f"under this config resolves to "
                f"{plan.backend!r}/caches_decisions="
                f"{pol.will_cache_decisions(cfg)} — gate on "
                f"decision_cache.supports_cache(cfg, policy) first")
        if bias is not None:
            raise ValueError("decision caching requires bias=None (the "
                             "cached plan could not account for a fresh "
                             "external bias)")
        if step is None:
            raise ValueError("decision caching needs a concrete step for "
                             "the reuse_every cadence")
    if plan.backend == "dense" or not cfg.active():
        out = dense_attention(q, k, v, scale, bias)
        if with_stats:
            zero = jnp.zeros(())
            return out, RippleStats(zero, zero, zero, zero)
        return out

    thetas = pol.thetas_for(cfg, step, total_steps, thetas)

    # Sharded fast path: an external bias would need its own spec, so
    # it stays on the replicated path.
    if mesh is not None and plan.sharded and bias is None:
        res = _sharded_pipeline(q, k, v, thetas, scale, plan=plan,
                                mesh=mesh, grid=grid, cfg=cfg,
                                grid_slice=grid_slice, policy=pol,
                                step=step, cached=cached_decision,
                                want_cache=want_cache,
                                total_steps=total_steps,
                                with_stats=with_stats)
        if want_cache:
            # The cached body injects faults inside shard_map.
            return res
        # The plain sharded path returns the bare output: inject here.
        if with_stats:
            return _inject_attn_nan(res[0], step), res[1]
        return _inject_attn_nan(res, step)

    if want_cache:
        out, stats, new_cache = _run_pipeline_cached(
            q, k, v, thetas, scale, plan=plan, grid=grid, cfg=cfg,
            grid_slice=grid_slice, policy=pol, step=step,
            cached=cached_decision, total_steps=total_steps)
        if with_stats:
            return out, new_cache, stats
        return out, new_cache

    out, stats = _run_pipeline(
        q, k, v, thetas, scale, bias, plan=plan, grid=grid, cfg=cfg,
        grid_slice=grid_slice, policy=pol)
    out = _inject_attn_nan(out, step)

    if with_stats:
        return out, stats
    return out
