"""Offline pattern search: static per-(layer, head) attention structure.

TIMERIPPLE's runtime policies pay a real per-step decision cost.
Sparse-vDiT observes that many (layer, head) pairs have *fixed*
sparsity structure — diagonal, multi-diagonal, sliding-window — that an
offline search can discover once, after which the runtime decision cost
drops to zero: the block map becomes a compile-time constant.
RainFusion adds a third "textural" redundancy branch next to the
spatial/temporal split.  This module is that subsystem (DESIGN.md §16):

* a library of parametric **templates** that render a boolean keep-mask
  (and its SKIP/FULL/PARTIAL block map) for *any* (T, H, W) grid and
  block shape — dense, frame-diagonal sliding window, multi-diagonal,
  spatial-local, temporal-stride, global-sink columns;
* an offline **search** (:func:`search_patterns`, driven by
  ``launch/pattern_search.py``) that scores every template per
  (layer, head) on calibration traffic through the dispatch path and
  classifies heads *static* (stable winner within tolerance) vs
  *dynamic*;
* a versioned JSON **artifact** persisted next to the autotune cache
  (same ``REPRO_*`` env-var idiom, same warn-and-regenerate hardening);
* two registered policies: ``static`` (constant maps, plan computed
  once at step 0 and replayed for the whole trajectory) and
  ``rainfusion`` (tri-branch: static heads get their searched
  spatial/temporal/textural mask, dynamic heads fall back to the
  adaptive ripple snap path).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import warnings
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import savings as savings_lib
from repro.core.policy import (ReuseDecision, ReusePolicy, RipplePolicy,
                               RippleStats, _keep_block_map, _zero_thetas,
                               register_policy, snap_operand)

__all__ = [
    "TemplateSpec", "template", "render_keep", "render_block_map",
    "block_map_np", "default_bank", "default_template", "branch_of",
    "HeadAssignment", "PatternArtifact", "PATTERN_SCHEMA",
    "pattern_artifact_path", "load_pattern_artifact",
    "save_pattern_artifact", "active_artifact", "set_active_artifact",
    "install_artifact", "use_artifact", "pattern_keep", "search_patterns",
    "StaticPatternPolicy", "RainFusionPolicy",
]

# Tile states, kept in sync with kernels/sparse/kernel.py by the parity
# test in tests/test_patterns.py (importing the kernel here would pull
# Pallas into every artifact load).
_SKIP, _FULL, _PARTIAL = 0, 1, 2


# ---------------------------------------------------------------------------
# Template library
# ---------------------------------------------------------------------------

def _token_coords(grid: Tuple[int, int, int]):
    """Per-token (frame, site, y, x) indices in the raster layout the
    rest of the repo uses: frame-major, then y, then x."""
    t, h, w = grid
    idx = np.arange(t * h * w)
    frame = idx // (h * w)
    site = idx % (h * w)
    return frame, site, site // w, site % w


def _render_dense(grid, **_):
    n = int(np.prod(grid))
    return np.ones((n, n), bool)


def _render_frame_diag(grid, window: int = 1, sink: int = 1):
    """Sliding window over frames (|f_q − f_k| < window) plus optional
    global-sink columns for the first ``sink`` frames."""
    f, _, _, _ = _token_coords(grid)
    keep = np.abs(f[:, None] - f[None, :]) < max(int(window), 1)
    if sink > 0:
        keep |= (f[None, :] < int(sink))
    return keep


def _render_multi_diag(grid, stride: int = 2, sink: int = 0):
    """Multi-diagonal over frames: keep frame pairs whose offset is a
    multiple of ``stride`` (Sparse-vDiT's strided-attention family)."""
    f, _, _, _ = _token_coords(grid)
    df = np.abs(f[:, None] - f[None, :])
    keep = (df % max(int(stride), 1)) == 0
    if sink > 0:
        keep |= (f[None, :] < int(sink))
    return keep


def _render_spatial_local(grid, radius: int = 1, sink_tokens: int = 0):
    """Within-frame Chebyshev neighbourhood: same frame and
    max(|Δx|, |Δy|) ≤ radius — the T=1 (image) family."""
    f, _, y, x = _token_coords(grid)
    r = max(int(radius), 0)
    keep = ((f[:, None] == f[None, :])
            & (np.abs(y[:, None] - y[None, :]) <= r)
            & (np.abs(x[:, None] - x[None, :]) <= r))
    if sink_tokens > 0:
        keep[:, :int(sink_tokens)] = True
    return keep


def _render_temporal_stride(grid, halo: int = 1, stride: int = 1):
    """Same spatial site (± halo in raster distance) across frames,
    optionally only at frame offsets that are multiples of ``stride``."""
    f, s, _, _ = _token_coords(grid)
    keep = np.abs(s[:, None] - s[None, :]) <= max(int(halo), 0)
    if stride > 1:
        keep &= (np.abs(f[:, None] - f[None, :]) % int(stride)) == 0
    return keep


def _render_global_sink(grid, tokens: int = 0):
    """Self-diagonal plus the first ``tokens`` global-sink columns
    (default: one frame's worth) — the textural/global family."""
    t, h, w = grid
    n = t * h * w
    cols = int(tokens) if tokens > 0 else h * w
    keep = np.eye(n, dtype=bool)
    keep[:, :min(cols, n)] = True
    return keep


TEMPLATE_FAMILIES: Dict[str, Callable[..., np.ndarray]] = {
    "dense": _render_dense,
    "frame_diag": _render_frame_diag,
    "multi_diag": _render_multi_diag,
    "spatial_local": _render_spatial_local,
    "temporal_stride": _render_temporal_stride,
    "global_sink": _render_global_sink,
}

# RainFusion's tri-branch routing: which redundancy branch a winning
# family corresponds to.  ``dense`` winners are by definition dynamic.
_BRANCH_OF = {
    "dense": "dynamic",
    "frame_diag": "spatial",
    "spatial_local": "spatial",
    "multi_diag": "temporal",
    "temporal_stride": "temporal",
    "global_sink": "textural",
}


@dataclasses.dataclass(frozen=True)
class TemplateSpec:
    """One parametric template: a family name plus a sorted tuple of
    (param, int-value) pairs — hashable so search can count winners."""

    family: str
    params: Tuple[Tuple[str, int], ...] = ()

    @property
    def label(self) -> str:
        kv = ",".join(f"{k}={v}" for k, v in self.params)
        return f"{self.family}({kv})" if kv else self.family

    def to_json(self) -> dict:
        return {"family": self.family, "params": dict(self.params)}

    @classmethod
    def from_json(cls, obj) -> "TemplateSpec":
        if not isinstance(obj, dict) or "family" not in obj:
            raise ValueError(f"malformed template spec: {obj!r}")
        fam = obj["family"]
        if fam not in TEMPLATE_FAMILIES:
            raise ValueError(f"unknown template family {fam!r}")
        params = obj.get("params", {})
        if not isinstance(params, dict):
            raise ValueError(f"malformed template params: {params!r}")
        return template(fam, **{str(k): int(v) for k, v in params.items()})


def template(family: str, **params: int) -> TemplateSpec:
    if family not in TEMPLATE_FAMILIES:
        raise ValueError(f"unknown template family {family!r}; known: "
                         f"{sorted(TEMPLATE_FAMILIES)}")
    return TemplateSpec(family,
                        tuple(sorted((k, int(v)) for k, v in params.items())))


def branch_of(spec: TemplateSpec) -> str:
    return _BRANCH_OF.get(spec.family, "textural")


def render_keep(spec: TemplateSpec,
                grid: Tuple[int, int, int]) -> np.ndarray:
    """(N, N) boolean keep-mask for ``spec`` on ``grid``.  The identity
    diagonal is always kept — no template may mask a token's own key."""
    keep = TEMPLATE_FAMILIES[spec.family](tuple(grid), **dict(spec.params))
    np.fill_diagonal(keep, True)
    return keep


def block_map_np(keep: np.ndarray, block_q: int, block_k: int) -> np.ndarray:
    """NumPy mirror of ``kernels.sparse.ops.block_map_from_keep`` (edge
    padding, same clamping) so template rendering stays a compile-time
    constant and the PARTIAL-free fast path is a *static* property."""
    n_q, n_k = keep.shape[-2:]
    bq = min(block_q, max(n_q, 1))
    bk = min(block_k, max(n_k, 1))
    nq, nk = -(-n_q // bq), -(-n_k // bk)
    widths = [(0, 0)] * (keep.ndim - 2) + [(0, nq * bq - n_q),
                                           (0, nk * bk - n_k)]
    tiled = np.pad(keep, widths, mode="edge") \
        .reshape(*keep.shape[:-2], nq, bq, nk, bk)
    any_keep = tiled.any(axis=(-3, -1))
    all_keep = tiled.all(axis=(-3, -1))
    return np.where(all_keep, _FULL,
                    np.where(any_keep, _PARTIAL, _SKIP)).astype(np.int32)


def render_block_map(spec: TemplateSpec, grid: Tuple[int, int, int],
                     block_shape: Tuple[int, int]) -> np.ndarray:
    return block_map_np(render_keep(spec, grid), *block_shape)


def template_skip_rate(spec: TemplateSpec, grid: Tuple[int, int, int],
                       block_shape: Tuple[int, int]) -> float:
    bm = render_block_map(spec, grid, block_shape)
    return float((bm == _SKIP).mean())


def default_template(grid: Tuple[int, int, int]) -> TemplateSpec:
    """Conservative fallback when no artifact entry covers a head:
    frame-diagonal + first-frame sink for video grids, a spatial window
    for T=1 image grids (spatial-only reuse)."""
    t, h, w = grid
    if t > 1:
        return template("frame_diag", window=1, sink=1)
    return template("spatial_local", radius=max(1, min(h, w) // 4))


def default_bank(grid: Tuple[int, int, int]) -> List[TemplateSpec]:
    """Candidate templates the search scores on ``grid``.  Video grids
    get the temporal families; T=1 grids get the spatial-only bank."""
    t, h, w = grid
    bank = [template("dense")]
    if t > 1:
        bank += [template("frame_diag", window=1, sink=1),
                 template("frame_diag", window=2, sink=1),
                 template("temporal_stride", halo=1),
                 template("temporal_stride", halo=w)]
        if t >= 4:
            bank.append(template("multi_diag", stride=2, sink=1))
    if min(h, w) >= 4:
        bank.append(template("spatial_local", radius=1))
        if min(h, w) >= 8:
            bank.append(template("spatial_local", radius=min(h, w) // 4))
    bank.append(template("global_sink"))
    return bank


# ---------------------------------------------------------------------------
# The versioned per-(layer, head) assignment artifact
# ---------------------------------------------------------------------------

PATTERN_SCHEMA = "repro-pattern/1"


@dataclasses.dataclass(frozen=True)
class HeadAssignment:
    """Search verdict for one (layer, head): the winning template, the
    static-vs-dynamic classification, and the evidence behind it."""

    spec: TemplateSpec
    static: bool
    branch: str          # spatial | temporal | textural | dynamic
    psnr_db: float       # worst-case PSNR of the winner vs reference
    skip_rate: float     # realized skipped-tile fraction at search block
    stability: float     # fraction of samples that voted for the winner

    def to_json(self) -> dict:
        return {"template": self.spec.to_json(), "static": self.static,
                "branch": self.branch, "psnr_db": round(self.psnr_db, 3),
                "skip_rate": round(self.skip_rate, 4),
                "stability": round(self.stability, 4)}

    @classmethod
    def from_json(cls, obj) -> "HeadAssignment":
        if not isinstance(obj, dict) or "template" not in obj:
            raise ValueError(f"malformed head assignment: {obj!r}")
        return cls(spec=TemplateSpec.from_json(obj["template"]),
                   static=bool(obj.get("static", False)),
                   branch=str(obj.get("branch", "dynamic")),
                   psnr_db=float(obj.get("psnr_db", 0.0)),
                   skip_rate=float(obj.get("skip_rate", 0.0)),
                   stability=float(obj.get("stability", 0.0)))


@dataclasses.dataclass
class PatternArtifact:
    """The searched per-(layer, head) assignment table.

    ``version`` is a content hash over the payload — it keys the plan
    cache and the serving bucket key, so swapping artifacts can never
    replay a stale compiled plan (DESIGN.md §16)."""

    grid: Tuple[int, int, int]
    block_shape: Tuple[int, int]
    tolerance_db: float
    heads: Dict[Tuple[int, int], HeadAssignment]
    meta: Dict[str, object] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        self.grid = tuple(int(g) for g in self.grid)
        self.block_shape = tuple(int(b) for b in self.block_shape)
        self._keep_cache: Dict[tuple, np.ndarray] = {}

    # -- content-hash version -------------------------------------------

    def _payload(self) -> dict:
        return {
            "schema": PATTERN_SCHEMA,
            "grid": list(self.grid),
            "block_shape": list(self.block_shape),
            "tolerance_db": self.tolerance_db,
            "heads": {f"{l}/{h}": a.to_json()
                      for (l, h), a in sorted(self.heads.items())},
            "meta": self.meta,
        }

    @property
    def version(self) -> str:
        blob = json.dumps(self._payload(), sort_keys=True).encode()
        return hashlib.sha1(blob).hexdigest()[:12]

    # -- lookups ---------------------------------------------------------

    @property
    def num_layers(self) -> int:
        return 1 + max((l for l, _ in self.heads), default=-1)

    @property
    def num_heads(self) -> int:
        return 1 + max((h for _, h in self.heads), default=-1)

    def static_fraction(self) -> float:
        if not self.heads:
            return 0.0
        return sum(a.static for a in self.heads.values()) / len(self.heads)

    def _majority(self, entries: Sequence[HeadAssignment]
                  ) -> Optional[HeadAssignment]:
        """Modal *static* assignment among ``entries`` (dynamic if the
        static votes don't reach half) — the layer-consolidation rule
        used when the caller can't name a layer (DESIGN.md §16)."""
        statics = [a for a in entries if a.static]
        if not entries or 2 * len(statics) < len(entries):
            return None
        counts: Dict[TemplateSpec, List[HeadAssignment]] = {}
        for a in statics:
            counts.setdefault(a.spec, []).append(a)
        spec, votes = max(counts.items(), key=lambda kv: len(kv[1]))
        return min(votes, key=lambda a: a.psnr_db)

    def assignment(self, layer: Optional[int],
                   head: int) -> Optional[HeadAssignment]:
        """Assignment for (layer, head): exact entry, else the majority
        vote over layers for this head, else the global majority.  None
        means dynamic / no stable pattern."""
        if layer is not None and (layer, head) in self.heads:
            a = self.heads[(layer, head)]
            return a if a.static else None
        per_head = [a for (l, h), a in self.heads.items() if h == head]
        got = self._majority(per_head)
        if got is not None or per_head:
            return got
        return self._majority(list(self.heads.values()))

    def keep_for(self, grid: Tuple[int, int, int], n_heads: int,
                 layer: Optional[int] = None) -> np.ndarray:
        """(n_heads, N, N) boolean keep — dynamic heads are all-True.
        Templates are parametric, so any runtime ``grid`` works, not
        just the grid the search ran on."""
        key = (tuple(grid), n_heads, layer)
        hit = self._keep_cache.get(key)
        if hit is not None:
            return hit
        n = int(np.prod(grid))
        keep = np.ones((n_heads, n, n), bool)
        for h in range(n_heads):
            a = self.assignment(layer, h)
            if a is not None:
                keep[h] = render_keep(a.spec, grid)
        self._keep_cache[key] = keep
        return keep

    def branches(self, n_heads: int,
                 layer: Optional[int] = None) -> List[str]:
        out = []
        for h in range(n_heads):
            a = self.assignment(layer, h)
            out.append(a.branch if a is not None else "dynamic")
        return out

    # -- serialization ---------------------------------------------------

    def to_json(self) -> dict:
        obj = self._payload()
        obj["version"] = self.version
        return obj

    @classmethod
    def from_json(cls, obj) -> "PatternArtifact":
        if not isinstance(obj, dict):
            raise ValueError(f"pattern artifact must be an object, got "
                             f"{type(obj).__name__}")
        schema = obj.get("schema")
        if schema != PATTERN_SCHEMA:
            raise ValueError(f"pattern artifact schema {schema!r} != "
                             f"{PATTERN_SCHEMA!r}")
        heads: Dict[Tuple[int, int], HeadAssignment] = {}
        raw = obj.get("heads", {})
        if not isinstance(raw, dict):
            raise ValueError(f"malformed heads table: {raw!r}")
        for key, val in raw.items():
            l, _, h = str(key).partition("/")
            heads[(int(l), int(h))] = HeadAssignment.from_json(val)
        grid = obj.get("grid", ())
        block = obj.get("block_shape", ())
        if len(grid) != 3 or len(block) != 2:
            raise ValueError(f"malformed grid/block_shape: "
                             f"{grid!r}/{block!r}")
        return cls(grid=tuple(grid), block_shape=tuple(block),
                   tolerance_db=float(obj.get("tolerance_db", 0.0)),
                   heads=heads, meta=obj.get("meta", {}) or {})


def pattern_artifact_path() -> str:
    """Resolution order mirrors ``autotune_cache_path``: the
    ``REPRO_PATTERN_ARTIFACT`` env var, else the user cache dir."""
    env = os.environ.get("REPRO_PATTERN_ARTIFACT", "")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "repro_timeripple", "patterns.json")


def load_pattern_artifact(path: Optional[str] = None
                          ) -> Optional[PatternArtifact]:
    """Load the artifact, hardened like the autotune cache: a missing
    file is None (quietly), corrupt/truncated JSON or a mismatched
    schema warns and returns None so callers regenerate instead of
    crashing the launcher (DESIGN.md §16)."""
    p = path or pattern_artifact_path()
    try:
        with open(p, "r", encoding="utf-8") as f:
            obj = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, ValueError) as e:
        warnings.warn(f"pattern artifact {p!r} is corrupt ({e}); ignoring "
                      f"it — re-run pattern_search to regenerate",
                      RuntimeWarning, stacklevel=2)
        return None
    try:
        return PatternArtifact.from_json(obj)
    except (ValueError, TypeError, KeyError) as e:
        warnings.warn(f"pattern artifact {p!r} does not match schema "
                      f"{PATTERN_SCHEMA!r} ({e}); ignoring it — re-run "
                      f"pattern_search to regenerate",
                      RuntimeWarning, stacklevel=2)
        return None


def save_pattern_artifact(artifact: PatternArtifact,
                          path: Optional[str] = None) -> str:
    """Atomic, durable write (tmp + fsync + rename via
    :func:`repro.utils.diskio.atomic_write_text`), same idiom as the
    autotune cache — an artifact produced just before a crash must be
    either fully present or absent on restart, never torn."""
    from repro.utils.diskio import atomic_write_text

    p = path or pattern_artifact_path()
    atomic_write_text(p, json.dumps(artifact.to_json(), indent=1,
                                    sort_keys=True))
    return p


# -- the process-wide active artifact ---------------------------------------

_ACTIVE: Optional[PatternArtifact] = None      # explicit install
_AUTO: Optional[Tuple[str, Optional[PatternArtifact]]] = None  # lazy load


def active_artifact() -> Optional[PatternArtifact]:
    """The artifact the pattern policies consult: an explicitly
    installed one, else a lazy load from :func:`pattern_artifact_path`
    (cached per resolved path, so flipping the env var takes effect)."""
    global _AUTO
    if _ACTIVE is not None:
        return _ACTIVE
    p = pattern_artifact_path()
    if _AUTO is None or _AUTO[0] != p:
        _AUTO = (p, load_pattern_artifact(p))
    return _AUTO[1]


def set_active_artifact(artifact: Optional[PatternArtifact]
                        ) -> Optional[PatternArtifact]:
    """Install (or with None, uninstall) the active artifact.  Flushes
    the dispatch plan cache — plans key on the artifact version, and a
    swap must never replay a stale compiled plan."""
    global _ACTIVE, _AUTO
    prev = _ACTIVE
    _ACTIVE = artifact
    _AUTO = None
    from repro.core import dispatch

    dispatch.clear_plan_cache()
    return prev


def install_artifact(path: str) -> PatternArtifact:
    """Load ``path`` and install it; raises on a missing or corrupt
    file — an *explicit* ``--pattern-artifact`` must fail loudly rather
    than silently serve the default templates."""
    art = load_pattern_artifact(path)
    if art is None:
        raise ValueError(f"no usable pattern artifact at {path!r}")
    set_active_artifact(art)
    return art


@contextlib.contextmanager
def use_artifact(artifact: Optional[PatternArtifact]):
    prev = set_active_artifact(artifact)
    try:
        yield artifact
    finally:
        set_active_artifact(prev)


def pattern_keep(artifact: Optional[PatternArtifact],
                 grid: Tuple[int, int, int], n_heads: int,
                 layer: Optional[int] = None) -> np.ndarray:
    """(n_heads, N, N) keep for the policies: the artifact's searched
    assignments when one is active, else the per-grid default template
    on every head (so ``--policy static`` stays runnable standalone)."""
    if artifact is not None:
        return artifact.keep_for(grid, n_heads, layer=layer)
    n = int(np.prod(grid))
    keep = render_keep(default_template(tuple(grid)), tuple(grid))
    return np.broadcast_to(keep, (n_heads, n, n))


# ---------------------------------------------------------------------------
# The offline search
# ---------------------------------------------------------------------------

def _psnr_per_head(ref: jax.Array, out: jax.Array) -> np.ndarray:
    """(H,) PSNR in dB of ``out`` vs ``ref`` for (B, H, N, d) outputs."""
    axes = tuple(i for i in range(ref.ndim) if i != 1)
    mse = jnp.mean(jnp.square(ref - out), axis=axes)
    peak = jnp.max(jnp.abs(ref), axis=axes)
    psnr = 10.0 * jnp.log10(jnp.square(peak) / jnp.maximum(mse, 1e-12))
    return np.asarray(jax.device_get(psnr), np.float64)


def search_patterns(samples: Iterable[Tuple[int, jax.Array, jax.Array,
                                            jax.Array]],
                    grid: Tuple[int, int, int], *,
                    block_shape: Tuple[int, int] = (128, 128),
                    tolerance_db: float = 30.0,
                    stability_min: float = 0.6,
                    bank: Optional[Sequence[TemplateSpec]] = None,
                    meta: Optional[Dict[str, object]] = None
                    ) -> PatternArtifact:
    """Score every template per (layer, head) on calibration traffic.

    ``samples`` yields ``(layer, q, k, v)`` with (B, H, N, d) operands —
    one entry per (layer, prompt, step) calibration point.  Every
    sample votes: the winner for a head is the highest-skip template
    whose PSNR vs reference attention stays ≥ ``tolerance_db``.  A head
    is **static** iff the same non-dense template wins on at least
    ``stability_min`` of its samples *and* its worst-case PSNR clears
    the tolerance; everything else is dynamic (DESIGN.md §16).
    """
    from repro.config.base import RippleConfig
    from repro.core.dispatch import attention_dispatch

    grid = tuple(int(g) for g in grid)
    bank = list(bank) if bank is not None else default_bank(grid)
    off = RippleConfig(enabled=False)

    # Pre-render each candidate once; scoring runs through the existing
    # dispatch path (reference backend + external bias) so the search
    # sees exactly the math the runtime will execute.
    biases = {}
    skips = {}
    density = {}  # masked score fraction — tie-breaks equal skip rates
    for spec in bank:
        keep = render_keep(spec, grid)
        biases[spec] = jnp.where(jnp.asarray(keep), 0.0,
                                 -jnp.inf).astype(jnp.float32)
        skips[spec] = template_skip_rate(spec, grid, block_shape)
        density[spec] = 1.0 - float(keep.mean())

    votes: Dict[Tuple[int, int], List[TemplateSpec]] = {}
    worst_psnr: Dict[Tuple[int, int, TemplateSpec], float] = {}
    n_samples = 0
    for layer, q, k, v in samples:
        n_samples += 1
        n_heads = q.shape[1]
        ref = attention_dispatch(q, k, v, grid=grid, cfg=off,
                                 backend="reference")
        scored = []
        for spec in bank:
            if spec.family == "dense":
                psnr = np.full((n_heads,), np.inf)
            else:
                out = attention_dispatch(q, k, v, grid=grid, cfg=off,
                                         backend="reference",
                                         bias=biases[spec])
                psnr = _psnr_per_head(ref, out)
            scored.append((spec, psnr))
            for h in range(n_heads):
                key = (int(layer), h, spec)
                worst_psnr[key] = min(worst_psnr.get(key, np.inf),
                                      float(psnr[h]))
        for h in range(n_heads):
            ok = [(spec, p[h]) for spec, p in scored
                  if p[h] >= tolerance_db]
            # Most skipped tiles wins; masked score fraction tie-breaks
            # (small grids tile coarsely enough that several templates
            # share a skip rate — including dense's zero).
            winner = max(ok, key=lambda sp: (skips[sp[0]],
                                             density[sp[0]]))[0] if ok \
                else template("dense")
            votes.setdefault((int(layer), h), []).append(winner)

    heads: Dict[Tuple[int, int], HeadAssignment] = {}
    for (layer, h), cast in votes.items():
        counts: Dict[TemplateSpec, int] = {}
        for spec in cast:
            counts[spec] = counts.get(spec, 0) + 1
        winner, n_votes = max(counts.items(), key=lambda kv: kv[1])
        stability = n_votes / len(cast)
        wpsnr = worst_psnr.get((layer, h, winner), 0.0)
        static = (winner.family != "dense"
                  and stability >= stability_min
                  and wpsnr >= tolerance_db)
        spec = winner if static else template("dense")
        heads[(layer, h)] = HeadAssignment(
            spec=spec, static=static,
            branch=branch_of(winner) if static else "dynamic",
            psnr_db=min(wpsnr, 1e9), skip_rate=skips[spec],
            stability=stability)

    info = {"samples": n_samples, "stability_min": stability_min,
            "bank": [s.label for s in bank]}
    info.update(meta or {})
    return PatternArtifact(grid=grid, block_shape=tuple(block_shape),
                           tolerance_db=float(tolerance_db), heads=heads,
                           meta=info)


# ---------------------------------------------------------------------------
# The policies
# ---------------------------------------------------------------------------

def _paste_grid_slice(keep: np.ndarray, n_tokens: int,
                      grid_slice: Optional[Tuple[int, int]]) -> np.ndarray:
    """Embed a (H, Ng, Ng) grid-segment keep into the full token range
    (text-prefix layouts): everything outside the video segment stays
    unmasked, same convention as ``svg_logit_bias``."""
    if grid_slice is None:
        return keep
    s, n = grid_slice
    full = np.ones(keep.shape[:-2] + (n_tokens, n_tokens), bool)
    full[..., s:s + n, s:s + n] = keep
    return full


class StaticPatternPolicy(ReusePolicy):
    """Constant searched masks: zero runtime decision cost.

    The keep-mask per head is a compile-time constant from the active
    pattern artifact (or the per-grid default template when none is
    installed), so decide() emits a constant bias/block map that XLA
    folds, and ``plan_once`` tells the decision cache to refresh at
    step 0 only — no Δ-checks, no theta schedule, no drift stat, one
    plan replayed for the whole trajectory (DESIGN.md §16).  When the
    rendered map has no PARTIAL tiles the N×N bias is dropped entirely
    and the block map alone carries the structure.
    """

    name = "static"
    emits_bias = True
    snaps_operands = False
    emits_block_map = True
    caches_decisions = True
    plan_once = True

    def __init__(self, artifact: Optional[PatternArtifact] = None,
                 layer: Optional[int] = None):
        self._artifact = artifact
        self.layer = layer

    def artifact(self) -> Optional[PatternArtifact]:
        return self._artifact if self._artifact is not None \
            else active_artifact()

    def plan_token(self, cfg=None):
        art = self.artifact()
        return art.version if art is not None else None

    def will_seq_shard(self, cfg):
        # Constant masks are row-separable by construction: each shard
        # renders its own bias rows (ring_bias_rows), and all-SKIP ring
        # hops fall straight out of the constant map.
        return True

    def thetas_for(self, cfg, step, total_steps, thetas=None):
        return _zero_thetas()

    def _keep(self, q, grid, grid_slice) -> np.ndarray:
        n_heads = q.shape[1] if q.ndim >= 4 else 1
        keep = pattern_keep(self.artifact(), grid, n_heads,
                            layer=self.layer)
        return _paste_grid_slice(keep, q.shape[-2], grid_slice)

    def decide(self, q, k, *, grid, cfg, thetas, bias=None, grid_slice=None,
               fused=False, block_shape=None, want_plan=False):
        keep_np = self._keep(q, grid, grid_slice)
        savings = jnp.asarray(1.0 - keep_np.mean(), jnp.float32)
        block_map = None
        need_bias = True
        if block_shape is not None:
            bmap_np = block_map_np(keep_np, *block_shape)
            block_map = jnp.asarray(bmap_np)
            # PARTIAL-free maps need no N×N bias at all — FULL tiles
            # ignore it and SKIP tiles never touch it.  This is a
            # static (python) property of the constant mask, so the
            # decision pytree stays stable across steps.
            need_bias = bool((bmap_np == _PARTIAL).any())
        if need_bias:
            pat = jnp.where(jnp.asarray(keep_np), 0.0,
                            -jnp.inf).astype(jnp.float32)
            bias = pat if bias is None else bias + pat
        return ReuseDecision(
            q=q, k=k, thetas=thetas, active_axes=(), bias=bias,
            savings=savings, block_map=block_map)

    def apply_decision(self, q, k, cached, *, grid, cfg, thetas,
                       grid_slice=None):
        # True passthrough: the base implementation re-derives savings
        # from the cached bias (a full pass over an N×N constant every
        # step); here the savings is a trace-time python constant and
        # the replay does zero per-step work — the whole point of
        # plan_once.  Pytree structure matches decide() exactly.
        keep_np = self._keep(q, grid, grid_slice)
        return ReuseDecision(
            q=q, k=k, thetas=thetas, active_axes=(), bias=cached.bias,
            savings=jnp.asarray(1.0 - keep_np.mean(), jnp.float32),
            block_map=cached.block_map)

    # -- ring/seq-shard hook (core/ring.py) -----------------------------

    def ring_bias_rows(self, q, k, *, grid, cfg, row_offset, n_rows):
        """Shard-local bias rows for the sparse ring path: slice the
        constant keep at this shard's row offset.  No collectives — the
        mask is position-determined, unlike svg's head classification."""
        n_heads = q.shape[1] if q.ndim >= 4 else 1
        keep = jnp.asarray(pattern_keep(self.artifact(), grid, n_heads,
                                        layer=self.layer))
        rows = jax.lax.dynamic_slice(
            keep, (0, row_offset, 0), (keep.shape[0], n_rows,
                                       keep.shape[-1]))
        bias = jnp.where(rows, 0.0, -jnp.inf).astype(jnp.float32)
        lead = q.shape[:-2] if q.ndim >= 4 else (q.shape[0],)
        return jnp.broadcast_to(bias, tuple(lead) + bias.shape[-2:])


class RainFusionPolicy(RipplePolicy):
    """Tri-branch routing: each head goes to its searched spatial /
    temporal / textural mask when the artifact classified it static,
    and falls back to the adaptive ripple snap path when dynamic.

    Static heads get the constant keep-mask (bias + block map) and
    *identity* snap sources; dynamic heads get ripple's windowed
    Δ-check snapping.  With no artifact installed every head is
    dynamic and the policy degrades to pure ripple."""

    name = "rainfusion"
    emits_bias = True
    emits_block_map = True

    def __init__(self, artifact: Optional[PatternArtifact] = None,
                 layer: Optional[int] = None):
        self._artifact = artifact
        self.layer = layer

    def artifact(self) -> Optional[PatternArtifact]:
        return self._artifact if self._artifact is not None \
            else active_artifact()

    def plan_token(self, cfg=None):
        art = self.artifact()
        return art.version if art is not None else None

    def will_emit_bias(self, cfg):
        return True

    def will_emit_block_map(self, cfg):
        return True

    def will_seq_shard(self, cfg):
        # Mixing the mask and snap paths on the ring would need both
        # fused shard-locally, which the ring driver doesn't implement.
        return False

    def _routing(self, q, grid, grid_slice):
        """(keep, dyn): the static heads' keep-mask (all-True rows for
        dynamic heads) and the per-head dynamic flag."""
        n_heads = q.shape[1] if q.ndim >= 4 else 1
        art = self.artifact()
        n = int(np.prod(grid))
        keep = np.ones((n_heads, n, n), bool)
        dyn = np.ones((n_heads,), bool)
        if art is not None:
            for h in range(n_heads):
                a = art.assignment(self.layer, h)
                if a is not None:
                    keep[h] = render_keep(a.spec, grid)
                    dyn[h] = False
        return _paste_grid_slice(keep, q.shape[-2], grid_slice), dyn

    def decide(self, q, k, *, grid, cfg, thetas, bias=None, grid_slice=None,
               fused=False, block_shape=None, want_plan=False):
        keep_np, dyn_np = self._routing(q, grid, grid_slice)
        active_axes = tuple(cfg.axes)
        # Static heads rewrite the masks below: their stats read the
        # masks, not the fused kernel's counts.
        q_s, q_mask, q_src, _ = snap_operand(
            q, cfg.snap_q, grid, thetas, cfg, active_axes, grid_slice,
            fused, want_src=want_plan)
        k_s, k_mask, k_src, _ = snap_operand(
            k, cfg.snap_k, grid, thetas, cfg, active_axes, grid_slice,
            fused, want_src=want_plan)
        if not dyn_np.all():
            # Static heads keep their original operands (their mask is
            # the whole decision); snapping applies to dynamic heads
            # only.  dyn aligns with the head axis (dim -3) of 4-D+
            # operands; 3-D operands route as one consolidated head.
            dyn = jnp.asarray(dyn_np)[:, None, None]
            q_s = jnp.where(dyn, q_s, q)
            k_s = jnp.where(dyn, k_s, k)
            if q_mask is not None:
                q_mask = jnp.logical_and(q_mask, dyn)
            if k_mask is not None:
                k_mask = jnp.logical_and(k_mask, dyn)
            if q_src is not None:
                q_src = jnp.where(dyn, q_src, _identity_src(q_src))
            if k_src is not None:
                k_src = jnp.where(dyn, k_src, _identity_src(k_src))
            pat = jnp.where(jnp.asarray(keep_np), 0.0,
                            -jnp.inf).astype(jnp.float32)
            bias = pat if bias is None else bias + pat
            block_map = _keep_block_map(jnp.asarray(keep_np), block_shape)
        else:
            block_map = None
        if q_mask is not None and k_mask is not None:
            savings = savings_lib.partial_score_savings(q_mask, k_mask)
        else:
            savings = jnp.zeros(())
        savings = savings + jnp.asarray(1.0 - keep_np.mean(), jnp.float32)
        return ReuseDecision(
            q=q_s, k=k_s, thetas=thetas, active_axes=active_axes,
            bias=bias, q_mask=q_mask, k_mask=k_mask, savings=savings,
            block_map=block_map, window=cfg.window,
            q_src=q_src, k_src=k_src)


    def stats(self, decision):
        # The base mask-path accounting recomputes savings from the
        # snap masks alone; decide() already folded the static heads'
        # pattern-mask term into decision.savings — keep it.
        s = super().stats(decision)
        return RippleStats(savings=decision.savings,
                           structural_savings=s.structural_savings,
                           q_snap_frac=s.q_snap_frac,
                           k_snap_frac=s.k_snap_frac)


def _identity_src(src: jax.Array) -> jax.Array:
    """Identity gather indices matching a snap-source map's shape: the
    replay becomes a no-op for the masked (static) heads."""
    n = src.shape[-2]
    iota = jnp.arange(n, dtype=src.dtype)[:, None]
    return jnp.broadcast_to(iota, src.shape)


register_policy(StaticPatternPolicy())
register_policy(RainFusionPolicy())
