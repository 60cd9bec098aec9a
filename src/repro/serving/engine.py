"""Serving engines.

DiffusionEngine: shape-bucketed continuous batching for text-to-image /
video generation.  Requests are keyed into a **bucket** by
``(latent_shape, steps, policy, reuse_every, seq_shards, txt_shape,
stream_every)``; the batcher drains buckets under an SLO-aware policy
(DESIGN.md §15): starvation aging first, then earliest-feasible-deadline
over deadline-carrying heads (EDF), then hottest (deepest) bucket for
deadline-less traffic — so heterogeneous traffic never pads or mixes
shapes inside one sampler invocation and tight SLOs are not stuck
behind deep hot buckets.  Admission control sheds requests at submit
time when they *provably* cannot meet their deadline
(:func:`repro.serving.slo.admission_decision`); shed requests cost zero
compute.  Each bucket owns a jitted (optionally mesh-sharded) sampler
obtained from ``sampler_factory`` and held in a bounded LRU of compiled
entries — the hottest bucket's sampler always survives eviction.
Per-request PRNG keys are threaded through ``sample_fn`` as a full
``(B, 2)`` key batch (vmap inside the sampler), so requests in one
batch never share sampler randomness.  TimeRipple's reuse schedule is
stateless per denoising step (no KV-style cache, paper Tbl. 2), which
is what makes this continuous batching safe: a bucket switch carries
zero eviction cost.  Attention inside the sampler routes through
``core.dispatch.attention_dispatch`` (DESIGN.md §8, §10); ``plan_fn``
lets the launcher log the resolved
:class:`~repro.core.dispatch.DispatchPlan` per bucket at first compile.

Streaming (DESIGN.md §15.3): a sampler factory that honours
``stream_every`` returns a *generator* sample_fn yielding intermediate
latents every K denoising steps; the engine publishes each chunk to
:meth:`DiffusionEngine.stream` subscribers as it lands and records
time-to-first-frame (``GenResult.ttff_s``, measured from submit) as a
first-class latency metric next to completion time.

Crash safety (DESIGN.md §18): with a ``journal`` attached the engine
writes a WAL record per lifecycle event (submitted before enqueue /
chunk / finished / shed); with a ``checkpoint_store`` each streamed
chunk additionally persists the per-request ``(x_t, decision-cache
state, step_offset)`` snapshot the sampler exposes via its chunk aux
(``aux["__ckpt__"]``).  A request carrying a ``resume`` payload lands
in a bucket keyed by its resume step and is served from the checkpoint
through the sampler's ``resume=`` keyword — bitwise-equal to the
uninterrupted run via the PR 7 ``step_offset``/``total_steps`` chunked
contract.  Failover-marked errors are never journaled as finished, so
a crashed replica's requests stay pending for warm restart.

LMEngine: KV-cache prefill + decode loop (used by the decode_32k /
long_500k shape cells and the LM serving example).
"""

from __future__ import annotations

import dataclasses
import inspect
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.guardrail import DegradationLadder, GuardrailConfig
from repro.serving import faults as fault_lib
from repro.serving import slo as slo_lib
from repro.serving.slo import ServiceEstimator, ShedError
from repro.utils.logging import get_logger

log = get_logger("serve")

# Error-message markers that mean "the *replica* failed, not the
# request" — the router requeues matching failures onto a healthy
# replica instead of surfacing them (§15.4, §17.4).  Substring-matched
# because errors cross the engine/router seam as strings.
FAILOVER_MARKERS = ("engine stopped", "watchdog")


def is_failover_error(msg: object) -> bool:
    """Does this error text name a replica-level failure (stop /
    watchdog trip) rather than a request-level one?"""
    text = str(msg)
    return any(marker in text for marker in FAILOVER_MARKERS)

# (latent_shape, steps, policy, reuse_every, seq_shards, txt_shape,
# stream_every); legacy single-sampler engines use steps=-1 so requests
# with differing ``steps`` still share the one compiled entry; policy is
# the reuse-policy name (None = the engine / sampler default), so
# requests under different sparsity strategies never share a compiled
# sampler; reuse_every is the decision-cache cadence (DESIGN.md §13;
# None = the sampler default) — it is baked into the compiled sampler's
# refresh cond, so mixed-cadence traffic must never share one compiled
# entry either; seq_shards is the context-parallel degree of the
# dispatch mesh at *submit* time (DESIGN.md §14) — a sampler compiled
# under a ring mesh runs a different program, so long-video requests
# route to the context-parallel replica shape and never share a
# compiled entry with unsharded traffic (and the mesh must not change
# while traffic is queued, §15.4); txt_shape is the text-embedding
# shape — two requests with different prompt lengths L can never stack
# into one ``(B, L, D)`` batch, so L is bucket identity, not a
# stack-time crash; stream_every is the chunked-delivery cadence
# (None = monolithic) — it changes the compiled chunk program; the
# trailing pattern token is the bucket policy's ``plan_token`` (the
# pattern artifact's content-hash version, DESIGN.md §16) — a
# ``static``/``rainfusion`` sampler bakes the artifact's constant masks
# into its compiled program, so traffic after an artifact swap must
# never share the stale compiled entry; the final element is the
# **resume step** (DESIGN.md §18): 0 for fresh traffic, the checkpoint
# step_offset for requests resuming mid-flight after a crash/failover —
# batchmates must share it (one sampler invocation has one step range),
# but it is *excluded* from the compiled-sampler LRU key (``key[:8]``)
# because the chunked sampler's traced step offset serves every resume
# point with one compiled program.
BucketKey = Tuple[Tuple[int, ...], int, Optional[str], Optional[int], int,
                  Tuple[int, ...], Optional[int], Optional[str], int]


def _seq_shards() -> int:
    """Seq-shard degree of the active dispatch mesh (1 = no context
    parallelism)."""
    from repro.core import dispatch as dispatch_lib

    mesh = dispatch_lib.active_dispatch_mesh()
    if mesh is not None and "seq" in mesh.axis_names:
        return int(mesh.shape["seq"])
    return 1


def _pattern_token(policy_name: Optional[str]) -> Optional[str]:
    """The bucket policy's plan token (pattern-artifact version), so an
    artifact swap between requests invalidates compiled samplers instead
    of silently replaying a stale constant plan."""
    from repro.core.policy import get_policy

    if not policy_name:
        return None
    try:
        pol = get_policy(policy_name)
    except KeyError:
        return None
    tok = getattr(pol, "plan_token", None)
    return tok(None) if callable(tok) else None


def _positional_arity(fn: Optional[Callable]) -> int:
    """How many positional arguments ``fn`` accepts.  Legacy
    two-argument factories / plan_fns keep working unchanged;
    policy-aware ones take a third, cadence-aware ones a fourth,
    streaming-aware ones a fifth.  A ``*args`` factory counts as 3 —
    exactly what such factories have received since the policy seam
    landed — so pre-cadence var-positional factories keep unpacking
    (shape, steps, policy); declare further named parameters to opt
    into the cadence / streaming arguments."""
    if fn is None:
        return 0
    try:
        params = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):
        return 2
    if any(p.kind == p.VAR_POSITIONAL for p in params):
        return 3
    return len([p for p in params
                if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)])


def _takes_policy(fn: Optional[Callable]) -> bool:
    """Does ``fn`` accept a third positional (policy) argument?"""
    return _positional_arity(fn) >= 3


@dataclasses.dataclass
class GenRequest:
    request_id: int
    txt: np.ndarray            # (L, txt_dim) precomputed embeddings
    steps: int = 50
    seed: int = 0
    guidance: float = 4.0
    # None -> the engine's default latent shape (single-shape traffic).
    latent_shape: Optional[Tuple[int, ...]] = None
    # Reuse-policy name for this request (core.policy registry); None ->
    # the engine's default policy.  Part of the bucket identity.
    policy: Optional[str] = None
    # Decision-cache cadence for this request (RippleConfig.reuse_every,
    # DESIGN.md §13); None -> the engine default.  Part of the bucket
    # identity — the cadence is compiled into the sampler's refresh cond.
    reuse_every: Optional[int] = None
    # Absolute wall-clock deadline (time.time() seconds; DESIGN.md §15).
    # None -> no SLO: never shed, scheduled behind deadline traffic by
    # depth.  Callers with relative SLOs stamp time.time() + slo_ms/1e3.
    deadline_s: Optional[float] = None
    # Chunked streaming cadence: deliver intermediate latents every K
    # denoising steps through DiffusionEngine.stream (§15.3).  None ->
    # monolithic delivery.  Part of the bucket identity.
    stream_every: Optional[int] = None
    # Mid-flight resume payload (DESIGN.md §18): ``{"step": int, "x":
    # latent array at that step, "dstate": decision-cache field->array
    # mapping or None}`` from a chunk-boundary checkpoint.  Attached by
    # the warm-restart recovery path and router failover, never by
    # clients; the resume step joins the bucket identity so batchmates
    # share one step range.
    resume: Optional[dict] = dataclasses.field(default=None, repr=False)
    # Was this request resubmitted from a journal recovery scan
    # (counts toward ``recovered_count``)?
    recovered: bool = False


@dataclasses.dataclass
class GenResult:
    request_id: int
    latents: Optional[np.ndarray]
    walltime_s: float
    error: Optional[str] = None
    batch_index: int = -1  # which sampler invocation served this request
    # Time-to-first-frame, measured from submit: first streamed chunk
    # for streaming buckets, completion for monolithic ones (§15.3).
    ttff_s: float = -1.0
    # Deadline outcome (None = the request carried no deadline).
    deadline_met: Optional[bool] = None
    # Was the serving bucket degraded below its requested reuse policy
    # by the guardrail ladder when this result was produced (§17.2)?
    degraded: bool = False
    # Reuse telemetry of the batch that served this request, read from
    # the sampler's aux: decision-cache hits/refreshes (§13) and, from
    # samplers built with ``reuse_stats``, the mean savings and q/k
    # snap fractions over every attention call.
    reuse: Optional[Dict[str, float]] = None


class DiffusionEngine:
    """Continuous-batching engine over bucketed samplers.

    ``sampler_factory(latent_shape, steps[, policy[, reuse_every[,
    stream_every]]]) -> sample_fn`` builds (and jits) the sampler for
    one bucket; ``sample_fn(latents0, txt, rngs)`` takes a ``(B, 2)``
    uint32 batch of per-request PRNG keys and returns latents or
    ``(latents, aux)`` with decision-cache telemetry — or, for
    streaming buckets, a *generator* yielding those per chunk.
    Factories (and ``plan_fn``) that accept a third positional argument
    receive the bucket's reuse-policy name (``GenRequest.policy`` /
    ``default_policy``); a fourth receives the decision-cache cadence
    (``GenRequest.reuse_every`` / ``default_reuse_every``, DESIGN.md
    §13); a fifth the streaming cadence (``GenRequest.stream_every``).
    Two-argument factories keep working.  The legacy single-sampler
    form ``DiffusionEngine(sample_fn, latent_shape)`` is still
    accepted: every request then lands in one default bucket.

    ``scheduler`` picks the drain policy (``"edf"`` default,
    ``"hottest"`` for the pre-SLO behaviour); ``admission_control``
    sheds provably-infeasible requests at submit with
    :class:`~repro.serving.slo.ShedError` (DESIGN.md §15.2).
    """

    def __init__(self, sample_fn: Optional[Callable] = None,
                 latent_shape: Optional[Tuple[int, ...]] = None,
                 *, sampler_factory: Optional[Callable] = None,
                 max_batch: int = 8, max_wait_s: float = 0.05,
                 max_compiled: int = 8, starve_after_s: float = 2.0,
                 attn_plan: Optional[Any] = None,
                 plan_fn: Optional[Callable] = None,
                 default_policy: Optional[str] = None,
                 default_reuse_every: Optional[int] = None,
                 scheduler: str = "edf",
                 admission_control: bool = True,
                 error_ttl_s: float = 60.0,
                 estimator: Optional[ServiceEstimator] = None,
                 guardrail: Any = None,
                 batch_timeout_s: Optional[float] = None,
                 max_retries: int = 1,
                 retry_backoff_s: float = 0.05,
                 bisect_on_error: bool = True,
                 journal: Any = None,
                 checkpoint_store: Any = None):
        if scheduler not in ("edf", "hottest"):
            raise ValueError(f"unknown scheduler {scheduler!r}")
        if sampler_factory is None:
            if sample_fn is None:
                raise ValueError("need sample_fn or sampler_factory")
            sampler_factory = lambda shape, steps: sample_fn  # noqa: E731
        self._factory = sampler_factory
        self._factory_arity = _positional_arity(sampler_factory)
        self._factory_takes_policy = self._factory_arity >= 3
        self._factory_takes_reuse = self._factory_arity >= 4
        self._factory_takes_stream = self._factory_arity >= 5
        self._plan_fn_takes_policy = _takes_policy(plan_fn)
        self._legacy = sample_fn is not None
        if default_policy is not None and not self._factory_takes_policy:
            raise ValueError(
                "default_policy is set but the sampler factory does not "
                "take a policy argument — it could not honour it")
        if default_reuse_every is not None and not self._factory_takes_reuse:
            raise ValueError(
                "default_reuse_every is set but the sampler factory does "
                "not take a reuse_every argument — it could not honour it")
        self.default_policy = default_policy
        self.default_reuse_every = default_reuse_every
        self.latent_shape = latent_shape
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self.max_compiled = max_compiled
        self.starve_after_s = starve_after_s
        self.scheduler = scheduler
        self.admission_control = admission_control
        self.error_ttl_s = error_ttl_s
        self.estimator = estimator if estimator is not None \
            else ServiceEstimator()
        # Guardrail ladder (§17.2): True -> own ladder with defaults, a
        # GuardrailConfig -> own ladder with it, a DegradationLadder ->
        # shared (router replicas share one so degraded state survives
        # failover), None/False -> sentinels not enforced.
        if guardrail is None or guardrail is False:
            self._ladder: Optional[DegradationLadder] = None
        elif isinstance(guardrail, DegradationLadder):
            self._ladder = guardrail
        elif isinstance(guardrail, GuardrailConfig):
            self._ladder = DegradationLadder(guardrail)
        elif guardrail is True:
            self._ladder = DegradationLadder()
        else:
            raise ValueError(f"guardrail must be True, a GuardrailConfig "
                             f"or a DegradationLadder, got {guardrail!r}")
        if self._ladder is not None and not self._factory_takes_policy:
            raise ValueError(
                "guardrail degradation rewrites the bucket policy, but "
                "this engine's sampler factory does not take a policy "
                "argument — it could not serve a degraded bucket")
        # Watchdog / retry / quarantine knobs (§17.4).  batch_timeout_s
        # is the hang-watchdog floor (scaled by the estimator's
        # timeout_hint once the bucket has observations); None disables
        # the watchdog and runs batches inline.
        self.batch_timeout_s = batch_timeout_s
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.bisect_on_error = bisect_on_error
        self.watchdog_trips = 0
        self.batch_retries = 0
        self.quarantined = 0
        self.attn_plan = attn_plan  # DispatchPlan metadata (or None)
        self.plan_fn = plan_fn      # (latent_shape, steps) -> DispatchPlan
        # bucket deques hold (enqueue_time, request) for starvation
        # aging, deadline lookup, and TTFF accounting
        self._buckets: Dict[BucketKey, deque] = {}
        self._compiled: "OrderedDict[BucketKey, Callable]" = OrderedDict()
        self._results: Dict[int, GenResult] = {}
        # errored results stay retrievable until their TTL so a caller
        # retrying after TimeoutError sees the original batch error —
        # rid -> eviction time (DESIGN.md §15.2)
        self._error_expiry: Dict[int, float] = {}
        # Tombstones for successes consumed by result(): rid -> eviction
        # time.  A stream() consumer still iterating when result() pops
        # the record needs a termination signal — without it the stream
        # hangs until TimeoutError.  Partials stay readable until the
        # tombstone expires.
        self._finished_expiry: Dict[int, float] = {}
        # streaming chunks: rid -> [np latents per delivered chunk]
        self._partials: Dict[int, List[np.ndarray]] = {}
        self._batches_served = 0
        self.shed_count = 0
        self.deadlines_met = 0
        self.deadlines_missed = 0
        # Crash-safety seam (DESIGN.md §18): a serving.journal.Journal
        # records request lifecycle events (submit-before-enqueue, WAL
        # order), a serving.journal.CheckpointStore persists chunk-
        # boundary generation state.  Replicas behind one router share
        # both (same journal directory).
        self._journal = journal
        self._store = checkpoint_store
        self.recovered_count = 0    # journal-recovered resubmissions seen
        self.resumed_count = 0      # requests served from a checkpoint
        self.last_resume_step = 0   # deepest checkpoint step resumed from
        self._lock = threading.Condition()
        self._stop = False
        self._thread: Optional[threading.Thread] = None

    # -- public API -----------------------------------------------------------

    def start(self):
        if self.attn_plan is not None:
            log.info("engine attention plan: %s", self.attn_plan.summary())
        with self._lock:
            self._stop = False  # allow stop() -> start() restart cycles
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self, drain: bool = True):
        """Stop the batcher.  With ``drain`` (default) every already-
        submitted request is served before the thread exits, so no result
        is orphaned; ``drain=False`` discards queued requests with an
        error result instead."""
        with self._lock:
            self._stop = True
            if not drain:
                for dq in self._buckets.values():
                    for _, r in dq:
                        self._results[r.request_id] = GenResult(
                            r.request_id, None, 0.0, error="engine stopped")
                        self._error_expiry[r.request_id] = (
                            time.time() + self.error_ttl_s)
                self._buckets.clear()
            self._lock.notify_all()
        if self._thread:
            self._thread.join()
            self._thread = None

    def healthy(self) -> bool:
        """Is the batcher thread alive and accepting work?"""
        with self._lock:
            stopped = self._stop
        return (not stopped and self._thread is not None
                and self._thread.is_alive())

    def submit(self, req: GenRequest):
        """Enqueue one request.  Raises
        :class:`~repro.serving.slo.ShedError` when admission control
        proves the request's deadline cannot be met under the current
        queue depth (shed at the door — zero compute spent).  Malformed
        requests raise ValueError here, at the door, instead of taking
        down a whole continuous batch inside the serve loop."""
        self._validate(req)
        if req.policy is not None and not self._factory_takes_policy:
            # Silently serving the default strategy while the bucket key
            # pretends otherwise would be worse than refusing.
            raise ValueError(
                f"request {req.request_id} sets policy={req.policy!r} but "
                "this engine's sampler factory does not take a policy "
                "argument")
        if req.reuse_every is not None and not self._factory_takes_reuse:
            raise ValueError(
                f"request {req.request_id} sets "
                f"reuse_every={req.reuse_every!r} but this engine's "
                "sampler factory does not take a reuse_every argument")
        if req.stream_every is not None and not self._factory_takes_stream:
            raise ValueError(
                f"request {req.request_id} sets "
                f"stream_every={req.stream_every!r} but this engine's "
                "sampler factory does not take a stream_every argument")
        key = self._bucket_key(req)
        now = time.time()
        # WAL order (§18): the lifecycle record lands *before* the
        # request is accepted, so a crash after this point can lose the
        # result but never the request.  A later shed/refusal is its own
        # record (or surfaces synchronously to the caller) — recovery
        # resubmits anything journaled-but-unfinished, at-least-once.
        if self._journal is not None:
            self._journal.record_submitted(req)
        try:
            with self._lock:
                if self._stop:
                    raise RuntimeError("engine is stopped")
                if self.admission_control and req.deadline_s is not None:
                    dq = self._buckets.get(key)
                    reason = slo_lib.admission_decision(
                        req.deadline_s, now, len(dq) if dq else 0,
                        self.max_batch, self.estimator.lower_bound(key))
                    if reason is not None:
                        self.shed_count += 1
                        raise ShedError(
                            f"request {req.request_id} shed: {reason}")
                self._buckets.setdefault(key, deque()).append((now, req))
                if req.recovered:
                    self.recovered_count += 1
                self._lock.notify_all()
        except ShedError as e:
            if self._journal is not None:
                self._journal.record_shed(req.request_id, str(e))
            raise

    def _validate(self, req: GenRequest) -> None:
        """Reject malformed requests at submit (§17 satellite): a bad
        field would otherwise stack fine, then crash the sampler and
        fail every batchmate."""
        rid = req.request_id
        if not isinstance(req.steps, (int, np.integer)) or req.steps <= 0:
            raise ValueError(
                f"request {rid}: steps must be a positive int, "
                f"got {req.steps!r}")
        if req.latent_shape is not None:
            shape = tuple(req.latent_shape)
            if not shape or not all(
                    isinstance(d, (int, np.integer)) and d > 0
                    for d in shape):
                raise ValueError(
                    f"request {rid}: latent_shape must be a non-empty "
                    f"tuple of positive ints, got {req.latent_shape!r}")
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(
                f"request {rid}: deadline_s must be an absolute "
                f"time.time() deadline (> 0), got {req.deadline_s!r}")
        if req.reuse_every is not None and req.reuse_every <= 0:
            raise ValueError(
                f"request {rid}: reuse_every must be positive, "
                f"got {req.reuse_every!r}")
        if req.stream_every is not None and req.stream_every <= 0:
            raise ValueError(
                f"request {rid}: stream_every must be positive, "
                f"got {req.stream_every!r}")
        if req.resume is not None:
            step = req.resume.get("step") if isinstance(req.resume, dict) \
                else None
            if (not isinstance(step, (int, np.integer)) or step < 0
                    or step >= req.steps or "x" not in req.resume):
                raise ValueError(
                    f"request {rid}: resume payload needs an int step in "
                    f"[0, steps) and an 'x' latent, got {req.resume!r:.80}")
            if req.stream_every and step % req.stream_every != 0:
                raise ValueError(
                    f"request {rid}: resume step {step} is not a chunk "
                    f"boundary of stream_every={req.stream_every} — the "
                    "chunk partitioning would diverge from the "
                    "uninterrupted run (DESIGN.md §18)")

    def result(self, request_id: int, timeout: float = 300.0) -> GenResult:
        deadline = time.time() + timeout
        with self._lock:
            self._evict_expired_errors_locked()
            while request_id not in self._results:
                remaining = deadline - time.time()
                # Clamp: a spurious wakeup near the deadline used to
                # hand Condition.wait a negative timeout.  Re-check the
                # dict after every wakeup so a result landing exactly at
                # the deadline is returned, not reported as a timeout.
                if remaining <= 0:
                    raise TimeoutError(f"request {request_id}")
                self._lock.wait(timeout=remaining)
            res = self._results[request_id]
            if res.error is None:
                self._results.pop(request_id)
                # Tombstone the consumed success (and keep its partials)
                # until the TTL so a stream() consumer that has not yet
                # finished iterating terminates cleanly instead of
                # hanging until TimeoutError.
                self._finished_expiry.setdefault(
                    request_id, time.time() + self.error_ttl_s)
            else:
                # Keep errored results retrievable until their TTL so a
                # caller that catches TimeoutError and retries gets the
                # original batch error, not a misleading second timeout.
                self._error_expiry.setdefault(
                    request_id, time.time() + self.error_ttl_s)
        if res.error is not None:
            raise RuntimeError(
                f"request {request_id} failed: {res.error}")
        return res

    def peek_result(self, request_id: int) -> Optional[GenResult]:
        """Non-blocking, non-consuming result lookup (router failover
        uses this to tell served from lost requests, §15.4)."""
        with self._lock:
            return self._results.get(request_id)

    def stream(self, request_id: int,
               timeout: float = 300.0) -> Iterator[np.ndarray]:
        """Yield intermediate latents for a streaming request as chunks
        land (one array per delivered chunk, in order), terminating when
        the final result is available — fetch it with :meth:`result`.
        Raises TimeoutError if no progress arrives within ``timeout``
        of the previous chunk."""
        idx = 0
        while True:
            chunk = None
            deadline = time.time() + timeout
            with self._lock:
                while True:
                    chunks = self._partials.get(request_id, ())
                    if len(chunks) > idx:
                        chunk = chunks[idx]
                        idx += 1
                        break
                    if (request_id in self._results
                            or request_id in self._finished_expiry):
                        return
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"request {request_id} stream stalled")
                    self._lock.wait(timeout=remaining)
            yield chunk  # outside the lock

    def pending(self) -> int:
        with self._lock:
            return sum(len(dq) for dq in self._buckets.values())

    def metrics(self) -> Dict[str, int]:
        """Serving counters (DESIGN.md §15/§17): batches served,
        admission sheds, deadline outcomes, robustness counters, and —
        when a guardrail ladder is attached — its degradation
        counters."""
        with self._lock:
            m = {"batches_served": self._batches_served,
                 "shed_count": self.shed_count,
                 "deadlines_met": self.deadlines_met,
                 "deadlines_missed": self.deadlines_missed,
                 "watchdog_trips": self.watchdog_trips,
                 "batch_retries": self.batch_retries,
                 "quarantined": self.quarantined,
                 "recovered_count": self.recovered_count,
                 "resumed_count": self.resumed_count,
                 "last_resume_step": self.last_resume_step}
        if self._journal is not None:
            m.update({k: int(v) for k, v in self._journal.metrics().items()})
        if self._store is not None:
            m.update({k: int(v) for k, v in self._store.metrics().items()})
        if self._ladder is not None:
            m.update(self._ladder.metrics())
        return m

    # -- batching loop ----------------------------------------------------------

    def _evict_expired_errors_locked(self):
        # Strictly-after comparison: a tombstone lives *through* its
        # expiry instant, so a result() retry landing exactly at TTL
        # expiry still gets the stored error instead of watching this
        # very call evict it and then reporting a spurious timeout.
        now = time.time()
        for rid in [r for r, exp in self._error_expiry.items() if exp < now]:
            self._error_expiry.pop(rid, None)
            self._results.pop(rid, None)
            self._partials.pop(rid, None)
        for rid in [r for r, exp in self._finished_expiry.items()
                    if exp < now]:
            self._finished_expiry.pop(rid, None)
            self._partials.pop(rid, None)

    def _bucket_key(self, req: GenRequest) -> BucketKey:
        shape = tuple(req.latent_shape) if req.latent_shape is not None \
            else tuple(self.latent_shape or ())
        if not shape:
            raise ValueError(f"request {req.request_id}: no latent shape "
                             "(set GenRequest.latent_shape or the engine "
                             "default)")
        return (shape, -1 if self._legacy else req.steps,
                req.policy or self.default_policy,
                req.reuse_every if req.reuse_every is not None
                else self.default_reuse_every,
                _seq_shards(),
                tuple(np.shape(req.txt)),
                req.stream_every,
                _pattern_token(req.policy or self.default_policy),
                int(req.resume["step"]) if req.resume else 0)

    def _next_bucket(self) -> Optional[BucketKey]:
        """SLO-aware drain order (DESIGN.md §15.1, logic in
        :func:`repro.serving.slo.choose_bucket`): starvation aging, then
        earliest-feasible-deadline, then hottest (deepest) bucket."""
        heads = {k: (dq[0][0], dq[0][1].deadline_s, len(dq))
                 for k, dq in self._buckets.items() if dq}
        return slo_lib.choose_bucket(
            heads, time.time(), scheduler=self.scheduler,
            starve_after_s=self.starve_after_s, estimator=self.estimator)

    def _take_batch(self):
        """Block for traffic, pick a bucket (see :meth:`_next_bucket`),
        linger briefly for batch-mates from the *same* bucket — the
        linger is event-driven (woken by ``submit``'s notify), never a
        poll loop.  Returns (key, batch of (enqueue_time, request)) or
        (None, None) once stopped and fully drained."""
        with self._lock:
            while True:
                key = self._next_bucket()
                if key is not None:
                    break
                if self._stop:
                    return None, None
                self._lock.wait(timeout=0.2)
            batch = [self._buckets[key].popleft()]
            deadline = time.time() + self.max_wait_s
            while len(batch) < self.max_batch and not self._stop:
                dq = self._buckets.get(key)
                while dq and len(batch) < self.max_batch:
                    batch.append(dq.popleft())
                if len(batch) >= self.max_batch:
                    break
                remaining = deadline - time.time()
                if remaining <= 0:
                    break
                self._lock.wait(timeout=remaining)
        return key, batch

    def _sampler(self, key: BucketKey) -> Callable:
        """Bounded LRU over compiled samplers; MRU (the hottest bucket)
        survives eviction.  The LRU is keyed on the bucket identity
        *minus* the resume step (``key[:8]``): the chunked sampler
        traces its step offset, so resumed traffic reuses the fresh
        bucket's compiled entry instead of recompiling per resume
        point."""
        key = key[:8]
        fn = self._compiled.get(key)
        if fn is None:
            shape, steps, pol, reuse = key[:4]
            stream = key[6]
            args = (shape, steps, pol, reuse,
                    stream)[:min(self._factory_arity, 5)]
            fn = self._factory(*args)
            self._compiled[key] = fn
            if self.plan_fn is not None:
                try:
                    plan = (self.plan_fn(shape, steps, pol)
                            if self._plan_fn_takes_policy
                            else self.plan_fn(shape, steps))
                    # None = no plan to report (e.g. UNet's multi-
                    # resolution attention has no single dispatch plan)
                    if plan is not None:
                        log.info("bucket %s plan: %s", key, plan.summary())
                except Exception:  # noqa: BLE001 — logging must not kill serving
                    log.exception("plan_fn failed for bucket %s", key)
        self._compiled.move_to_end(key)
        while len(self._compiled) > self.max_compiled:
            evicted, _ = self._compiled.popitem(last=False)
            log.info("evicted compiled sampler for bucket %s", evicted)
        return fn

    def _publish_chunk(self, batch, lat_np: np.ndarray, pub: Dict,
                       chunk_idx: int, abandoned: threading.Event):
        """Deliver one streamed chunk to every request's subscribers and
        stamp TTFF on first delivery.  ``pub`` survives re-serves (§17):
        chunks a previous attempt already delivered are not re-published
        (``pub["count"]``), and a watchdog-abandoned worker's late
        chunks are dropped (``abandoned``)."""
        now = time.time()
        with self._lock:
            if abandoned.is_set():
                return
            for i, (t_enq, r) in enumerate(batch):
                if chunk_idx < pub["count"].get(r.request_id, 0):
                    continue
                pub["ttff"].setdefault(r.request_id, now - t_enq)
                self._partials.setdefault(r.request_id, []).append(lat_np[i])
                pub["count"][r.request_id] = chunk_idx + 1
            self._lock.notify_all()

    @staticmethod
    def _split_out(out) -> Tuple[Any, Optional[dict]]:
        """(latents, aux) vs bare latents."""
        if isinstance(out, (tuple, list)) and len(out) == 2:
            return out[0], out[1]
        return out, None

    def _read_aux(self, key: BucketKey,
                  aux: Optional[dict]) -> Optional[Dict[str, float]]:
        """Log and return the batch's reuse telemetry: decision-cache
        hits/refreshes (DESIGN.md §13), so the amortization is
        observable in serving, not just benches, and the
        ``reuse_*`` stats of samplers built with ``reuse_stats``."""
        if not aux:
            return None
        hits = int(jax.device_get(aux.get("cache_hits", 0)))
        refr = int(jax.device_get(aux.get("cache_refreshes", 0)))
        reuse = {"cache_hits": hits, "cache_refreshes": refr}
        if hits + refr:
            log.info(
                "bucket %s decision cache: %d hits / %d refreshes "
                "(hit rate %.2f)", key, hits, refr,
                hits / max(hits + refr, 1))
        stats = {k: float(jax.device_get(v)) for k, v in aux.items()
                 if k.startswith("reuse_")}
        if stats:
            reuse.update(stats)
            log.info("bucket %s reuse: %s", key,
                     ", ".join(f"{k[6:]} {v:.4f}"
                               for k, v in stats.items()))
        if "ring_elided_hops" in aux:
            # Context-parallel telemetry (DESIGN.md §14): ring hops the
            # block map let the seq shards skip.
            log.info("bucket %s ring: %d elided hop(s)", key,
                     int(jax.device_get(aux["ring_elided_hops"])))
        return reuse

    # -- guardrail / watchdog serve path (DESIGN.md §17) ----------------------

    @staticmethod
    def _family(key: BucketKey):
        """Bucket identity minus the policy and its pattern token — the
        unit the degradation ladder keys on: every policy rung of one
        (shape, steps, cadence, shards, txt, stream) family shares one
        health record."""
        return key[:2] + key[3:7]

    @staticmethod
    def _rekey(key: BucketKey, policy: Optional[str]) -> BucketKey:
        """The same bucket one ladder rung down: policy and pattern
        token rewritten, everything else identical — so the degraded
        bucket compiles its own sampler instead of replaying the
        tripped program."""
        return (key[:2] + (policy,) + key[3:7]
                + (_pattern_token(policy),) + key[8:])

    def _sentinel_verdict(self, lat: Optional[np.ndarray],
                          aux: Optional[dict]) -> Optional[str]:
        """Read the batch's sentinels: ``None`` when clean, else a trip
        reason.  The host ``isfinite`` over the returned latents covers
        samplers that thread no cache; the aux counters cover the
        in-graph sentinels (latent carry + attention-output carry +
        drift probe)."""
        gcfg = self._ladder.config
        if lat is not None and not np.all(np.isfinite(lat)):
            return "non-finite final latents"
        if aux:
            nf = 0
            for k in ("latent_nonfinite", "sentinel_nonfinite"):
                if k in aux:
                    nf += int(jax.device_get(aux[k]))
            if nf > gcfg.max_nonfinite:
                return f"{nf} non-finite sentinel entr(ies)"
            if "sentinel_drift" in aux:
                drift = float(jax.device_get(aux["sentinel_drift"]))
                if not np.isfinite(drift):
                    return "non-finite drift probe"
                if gcfg.drift_tol > 0 and drift > gcfg.drift_tol:
                    return (f"drift probe {drift:.3g} > "
                            f"tol {gcfg.drift_tol:.3g}")
        return None

    # -- crash-safety seam (DESIGN.md §18) ------------------------------------

    @staticmethod
    def _accepts_resume(fn: Callable) -> bool:
        """Does this sampler take a ``resume=`` keyword?  Factories
        that predate the checkpoint seam don't — resumed requests then
        fall back to deterministic replay-from-step-0, which is slower
        but returns identical latents."""
        try:
            params = inspect.signature(fn).parameters
        except (TypeError, ValueError):
            return False
        return "resume" in params or any(
            p.kind == p.VAR_KEYWORD for p in params.values())

    def _assemble_resume(self, key: BucketKey,
                         batch: List[Tuple[float, GenRequest]],
                         fn: Callable) -> Optional[Dict[str, Any]]:
        """Build the batch-level resume payload ``{"x", "step",
        "dstate"}`` from the per-request checkpoints, or ``None`` for
        fresh traffic / samplers without resume support.  The bucket
        key pins the resume step, so every batchmate shares it; their
        latents stack on axis 0 and their decision-state slices merge
        back along the batch axis."""
        step = key[8] if len(key) > 8 else 0
        if step <= 0:
            return None
        payloads = [r.resume for _, r in batch]
        if any(p is None for p in payloads):
            return None  # defensive: bucket identity should prevent this
        if not self._accepts_resume(fn):
            log.warning(
                "bucket %s: sampler takes no resume argument; replaying "
                "%d checkpointed request(s) from step 0", key, len(batch))
            return None
        from repro.core import decision_cache

        xs = jnp.stack([jnp.asarray(p["x"]) for p in payloads])
        dstates = [p.get("dstate") for p in payloads]
        merged = None
        if all(d is not None for d in dstates):
            merged = decision_cache.merge_states(
                [decision_cache.state_from_arrays(d) for d in dstates])
        elif any(d is not None for d in dstates):
            log.warning("bucket %s: mixed cache/cache-less checkpoints "
                        "in one batch; resuming without decision state",
                        key)
        return {"x": xs, "step": int(step), "dstate": merged}

    def _record_chunk(self, key: BucketKey,
                      batch: List[Tuple[float, GenRequest]],
                      lat_np: np.ndarray, ck: Optional[Dict],
                      ci: int, abandoned: threading.Event):
        """Durable side of one delivered chunk (§18): a ``chunk``
        journal record per request, and — when a checkpoint store is
        attached, the sampler exposed its ``__ckpt__`` state, and the
        bucket is unsharded — the per-request ``(x_t, dstate, step)``
        checkpoint.  Runs outside the engine lock (fsync latency must
        not block submitters); a watchdog-abandoned zombie writes
        nothing."""
        if (self._journal is None and self._store is None) \
                or abandoned.is_set():
            return
        step = ck.get("step") if ck else None
        stream = key[6] or 0
        base_ci = (key[8] // stream) if len(key) > 8 and stream else 0
        if self._journal is not None:
            for _, r in batch:
                try:
                    self._journal.record_chunk(r.request_id, base_ci + ci,
                                               step)
                except RuntimeError:
                    return  # journal closed mid-shutdown
        steps = key[1]
        if (self._store is None or ck is None or step is None
                or key[4] != 1 or (steps > 0 and int(step) >= steps)):
            # No store, no sampler state, a context-parallel bucket
            # (per-shard state cannot be re-sliced per request), or the
            # final chunk (the request is about to finish and the
            # checkpoint would be discarded immediately).
            return
        arrays = None
        dstate = ck.get("dstate")
        if dstate is not None:
            from repro.core import decision_cache

            arrays = decision_cache.state_to_arrays(dstate)
            if any(a is not None and a.ndim < 2 for a in arrays.values()):
                # Not a layer-stacked batched state: no batch axis to
                # slice per request — skip checkpointing, keep serving.
                arrays = None
        for i, (_, r) in enumerate(batch):
            per = None
            if arrays is not None:
                # Batch axis 1 of every (layers, batch, ...) leaf,
                # kept as a size-1 dim so merge_states is the inverse.
                per = {k: (None if v is None else v[:, i:i + 1])
                       for k, v in arrays.items()}
            try:
                self._store.put(r.request_id, step=int(step),
                                x=lat_np[i], seed=r.seed,
                                bucket=key[:8], dstate=per)
            except OSError as e:
                log.warning("checkpoint write failed for request %d: %s",
                            r.request_id, e)

    def _run_batch(self, key: BucketKey,
                   batch: List[Tuple[float, GenRequest]], pub: Dict,
                   abandoned: threading.Event):
        """Run one sampler invocation, optionally under the hang
        watchdog.  Returns ``(res, hung, budget)`` where ``res`` holds
        ``lat``/``aux`` on success, ``err`` (the exception) on failure,
        or ``sentinel`` (a trip reason) when a streamed chunk went
        non-finite — caught *before* publication, so subscribers never
        see the bad frames."""
        res: Dict[str, Any] = {}

        def work():
            try:
                fault = fault_lib.active_faults()
                if fault is not None:
                    fault.check_poison([r.request_id for _, r in batch])
                    fault.maybe_raise()
                    if fault.maybe_hang():
                        return  # hung past the watchdog; batch is lost
                fn = self._sampler(key)
                shape = key[0]
                txt = jnp.stack([jnp.asarray(r.txt) for _, r in batch])
                rngs = jnp.stack([jax.random.PRNGKey(r.seed)
                                  for _, r in batch])
                resume = self._assemble_resume(key, batch, fn)
                if resume is not None:
                    # Mid-flight resume (§18): the checkpointed x_t
                    # replaces the initial noise and the sampler starts
                    # at the checkpoint's step offset with the cached
                    # decision state — the remaining schedule slice is
                    # bitwise-identical to the uninterrupted run.
                    noise = resume.pop("x")
                    out = fn(noise, txt, rngs, resume=resume)
                else:
                    noise = jax.vmap(
                        lambda k: jax.random.normal(k, shape))(rngs)
                    # The full (B, 2) key batch goes to the sampler —
                    # every request keeps its own randomness inside one
                    # batch.
                    out = fn(noise, txt, rngs)
                if inspect.isgenerator(out):
                    # Streaming bucket (§15.3): each yielded chunk is
                    # published to stream() subscribers as it lands; the
                    # last chunk is the final latents.
                    lat = aux = None
                    for ci, chunk in enumerate(out):
                        lat, aux = self._split_out(chunk)
                        ck = None
                        if isinstance(aux, dict):
                            ck = aux.pop("__ckpt__", None)
                        lat = np.asarray(jax.device_get(lat))
                        if (self._ladder is not None
                                and not np.all(np.isfinite(lat))):
                            res["sentinel"] = \
                                f"non-finite streamed chunk {ci}"
                            return
                        self._publish_chunk(batch, lat, pub, ci, abandoned)
                        self._record_chunk(key, batch, lat, ck, ci,
                                           abandoned)
                    if lat is None:
                        raise RuntimeError(
                            "streaming sampler yielded nothing")
                else:
                    lat, aux = self._split_out(out)
                    lat = np.asarray(jax.device_get(lat))
                res["lat"], res["aux"] = lat, aux
            except Exception as e:  # noqa: BLE001 — fail the batch, not the engine
                log.exception("bucket %s batch failed", key)
                res["err"] = e

        if self.batch_timeout_s is None:
            work()
            return res, False, 0.0
        budget = self.estimator.timeout_hint(key, self.batch_timeout_s)
        worker = threading.Thread(target=work, daemon=True)
        worker.start()
        worker.join(timeout=budget)
        return res, worker.is_alive(), budget

    def _trip_watchdog(self, key: BucketKey,
                       batch: List[Tuple[float, GenRequest]],
                       budget: float, abandoned: threading.Event):
        """A batch hung past its watchdog budget: the worker cannot be
        killed (it is stuck inside compiled code), so the *replica*
        steps down — mark the engine stopped (``healthy()`` goes False),
        error the hung and queued requests with failover-marked messages
        so the router requeues them elsewhere, and suppress any late
        chunk publishes from the zombie worker."""
        abandoned.set()
        log.error("watchdog: bucket %s batch of %d hung past %.1fs — "
                  "marking replica unhealthy", key, len(batch), budget)
        now = time.time()
        with self._lock:
            self.watchdog_trips += 1
            self._stop = True
            err = f"watchdog: batch hung after {budget:.1f}s"
            for t_enq, r in batch:
                if r.deadline_s is not None:
                    self.deadlines_missed += 1
                self._results[r.request_id] = GenResult(
                    r.request_id, None, now - t_enq, error=err,
                    deadline_met=False if r.deadline_s is not None
                    else None)
                self._error_expiry[r.request_id] = now + self.error_ttl_s
            for dq in self._buckets.values():
                for _, r in dq:
                    self._results[r.request_id] = GenResult(
                        r.request_id, None, 0.0,
                        error="engine stopped (watchdog)")
                    self._error_expiry[r.request_id] = (
                        now + self.error_ttl_s)
            self._buckets.clear()
            self._lock.notify_all()

    def _publish_batch(self, key: BucketKey,
                       batch: List[Tuple[float, GenRequest]],
                       lat: np.ndarray, dt: float, pub: Dict,
                       err: Optional[str], degraded: bool,
                       reuse: Optional[Dict[str, float]] = None):
        now = time.time()
        with self._lock:
            bi = self._batches_served
            self._batches_served += 1
            for i, (t_enq, r) in enumerate(batch):
                met = None
                if r.deadline_s is not None:
                    met = err is None and now <= r.deadline_s
                    if met:
                        self.deadlines_met += 1
                    else:
                        self.deadlines_missed += 1
                self._results[r.request_id] = GenResult(
                    r.request_id, None if err else lat[i], dt, error=err,
                    batch_index=bi,
                    ttff_s=pub["ttff"].get(r.request_id,
                                           -1.0 if err else now - t_enq),
                    deadline_met=met, degraded=degraded,
                    reuse=None if err else reuse)
                if err is not None:
                    self._error_expiry[r.request_id] = (
                        time.time() + self.error_ttl_s)
            if err is None and len(key) > 8 and key[8] > 0:
                self.resumed_count += len(batch)
                self.last_resume_step = max(self.last_resume_step,
                                            int(key[8]))
            self._lock.notify_all()
        # Durable terminal records, outside the lock (§18).  Failover-
        # marked errors (replica died, watchdog) are *not* journaled as
        # finished — the request is still owed a result, so it must stay
        # pending for recovery; request-level errors (poison, quarantine,
        # guardrail dead-ends) are, so a restart never resurrects them.
        if self._journal is not None or self._store is not None:
            for _, r in batch:
                if err is not None and is_failover_error(err):
                    continue
                if self._journal is not None:
                    try:
                        self._journal.record_finished(r.request_id,
                                                      error=err)
                    except RuntimeError:
                        break  # journal closed mid-shutdown
                if self._store is not None:
                    self._store.discard(r.request_id)

    def _serve(self, key: BucketKey, batch: List[Tuple[float, GenRequest]]):
        pub: Dict[str, Dict] = {"ttff": {}, "count": {}}
        self._serve_rec(key, batch, 0, pub, threading.Event())

    def _serve_rec(self, key: BucketKey,
                   batch: List[Tuple[float, GenRequest]], depth: int,
                   pub: Dict, abandoned: threading.Event):
        """Serve one (sub-)batch with the full §17 escalation chain:
        sentinel trip -> degrade one ladder rung and re-serve; hang ->
        watchdog (replica down); transient error -> retry with backoff,
        then bisect so a single poison request is quarantined alone
        while its batchmates succeed."""
        t0 = time.time()
        base_pol = key[2]
        fam = self._family(key)
        attempt = 0
        while True:
            eff_key = key
            if self._ladder is not None:
                eff_pol, _probing = self._ladder.effective_policy(
                    fam, base_pol)
                if eff_pol != base_pol:
                    eff_key = self._rekey(key, eff_pol)
            res, hung, budget = self._run_batch(eff_key, batch, pub,
                                                abandoned)
            if hung:
                self._trip_watchdog(eff_key, batch, budget, abandoned)
                return
            sent = res.get("sentinel")
            exc = res.get("err")
            if exc is None and sent is None and "lat" not in res:
                exc = RuntimeError("sampler worker produced no output")
            if exc is None and sent is None and self._ladder is not None:
                sent = self._sentinel_verdict(res.get("lat"),
                                              res.get("aux"))
            if sent is not None and self._ladder is not None:
                nxt = self._ladder.trip(fam, base_pol)
                if nxt is not None:
                    log.warning(
                        "bucket %s guardrail trip (%s): degrading to %r "
                        "and re-serving", key, sent, nxt)
                    continue
                exc = RuntimeError(
                    f"guardrail: {sent} at the dense floor — no "
                    "degradation step left")
            elif sent is None and exc is None and self._ladder is not None:
                self._ladder.record_clean(fam)
            if exc is None:
                dt = time.time() - t0
                reuse = self._read_aux(eff_key, res.get("aux"))
                self.estimator.observe(key, dt)
                if eff_key != key:
                    self.estimator.observe(eff_key, dt)
                self._publish_batch(
                    key, batch, res["lat"], dt, pub, None,
                    degraded=(self._ladder is not None
                              and self._ladder.degraded(fam)),
                    reuse=reuse)
                log.info("served bucket %s batch of %d in %.2fs%s", key,
                         len(batch), dt,
                         " (degraded)" if eff_key != key else "")
                return
            # Error path.  Sentinel dead-ends (dense floor) are not
            # transient: no retry, no bisection — every rung failed.
            attempt += 1
            if sent is None and attempt <= self.max_retries:
                backoff = self.retry_backoff_s * (2 ** (attempt - 1))
                with self._lock:
                    self.batch_retries += 1
                log.warning("bucket %s batch failed (attempt %d/%d), "
                            "retrying in %.2fs: %r", key, attempt,
                            self.max_retries + 1, backoff, exc)
                time.sleep(backoff)
                continue
            if sent is None and self.bisect_on_error and len(batch) > 1:
                mid = len(batch) // 2
                log.warning("bucket %s: bisecting failed batch of %d to "
                            "isolate the poison request", key, len(batch))
                self._serve_rec(key, batch[:mid], depth + 1, pub,
                                abandoned)
                self._serve_rec(key, batch[mid:], depth + 1, pub,
                                abandoned)
                return
            if depth > 0 and len(batch) == 1:
                # Bisection bottomed out on one request: quarantine it —
                # it fails alone, its former batchmates already served.
                with self._lock:
                    self.quarantined += 1
                log.error("bucket %s: request %d quarantined after "
                          "bisection: %r", key,
                          batch[0][1].request_id, exc)
            self._publish_batch(key, batch, None, time.time() - t0, pub,
                                repr(exc), degraded=False)
            return

    def _loop(self):
        while True:
            key, batch = self._take_batch()
            if key is None:
                return  # stopped and drained
            self._serve(key, batch)
            fault = fault_lib.active_faults()
            if fault is not None:
                fault.maybe_corrupt_artifact(self._batches_served)


class LMEngine:
    """Prefill + decode serving for the LM family."""

    def __init__(self, prefill_fn: Callable, decode_fn: Callable,
                 max_len: int):
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.max_len = max_len

    def generate(self, tokens: jax.Array, num_new: int,
                 temperature: float = 0.0, rng=None) -> jax.Array:
        """tokens: (B, S) prompt -> (B, num_new) continuations (greedy or
        temperature sampling).  Temperature sampling requires an
        explicit ``rng`` key; ``prompt + num_new`` must fit the engine's
        ``max_len`` KV budget."""
        B, S = tokens.shape
        if S + num_new > self.max_len:
            raise ValueError(
                f"prompt ({S}) + num_new ({num_new}) = {S + num_new} "
                f"exceeds max_len={self.max_len}; the KV cache was "
                f"allocated for max_len positions")
        if temperature > 0 and rng is None:
            raise ValueError(
                "temperature > 0 requires an explicit rng key "
                "(jax.random.split(None) is not a key)")
        logits, cache = self.prefill_fn(tokens)
        out = []
        index = jnp.asarray(S, jnp.int32)
        cur = None
        for i in range(num_new):
            if temperature > 0:
                rng, sub = jax.random.split(rng)
                nxt = jax.random.categorical(sub, logits[:, -1] / temperature)
            else:
                nxt = jnp.argmax(logits[:, -1], axis=-1)
            out.append(nxt)
            cur = nxt[:, None]
            logits, cache = self.decode_fn(cur, cache, index)
            index = index + 1
        return jnp.stack(out, axis=1)
