"""SLO serving tests (DESIGN.md §15): admission-control proofs,
deadline-aware bucket choice, EDF vs hottest under overload, and the
multi-replica router's balancing / shed propagation / failover.

The scheduler comparison is the PR's acceptance gate: on a crafted
overload trace EDF must meet *strictly more* deadlines than the legacy
hottest-first drain."""

import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.engine import DiffusionEngine, GenRequest
from repro.serving.router import Router
from repro.serving.slo import (ServiceEstimator, ShedError,
                               _batches_needed, admission_decision,
                               choose_bucket)


def _txt(val, tokens=1, dim=1):
    return np.full((tokens, dim), float(val), np.float32)


class TestServiceEstimator:
    def test_unknown_bucket_has_no_estimate(self):
        est = ServiceEstimator()
        assert est.lower_bound("k") is None
        assert est.expected("k") is None

    def test_lower_bound_is_min_expected_is_ewma(self):
        est = ServiceEstimator(alpha=0.5)
        est.observe("k", 2.0)
        est.observe("k", 1.0)
        est.observe("k", 3.0)
        assert est.lower_bound("k") == 1.0
        # EWMA: 2.0 -> 1.5 -> 2.25
        assert est.expected("k") == pytest.approx(2.25)

    def test_buckets_are_independent(self):
        est = ServiceEstimator()
        est.observe("a", 1.0)
        assert est.lower_bound("b") is None

    def test_timeout_hint_scales_with_ewma_above_floor(self):
        """Watchdog budget (§17.4): the caller's floor until the bucket
        has observations, then mult x the EWMA — never below the floor."""
        est = ServiceEstimator()
        assert est.timeout_hint("k", 5.0) == 5.0
        est.observe("k", 2.0)
        assert est.timeout_hint("k", 5.0) == pytest.approx(16.0)
        assert est.timeout_hint("k", 60.0) == 60.0  # floor still wins
        assert est.timeout_hint("k", 5.0, mult=2.0) == pytest.approx(5.0)


class TestAdmissionDecision:
    NOW = 1000.0

    def test_no_deadline_always_admits(self):
        assert admission_decision(None, self.NOW, 50, 1, 10.0) is None

    def test_expired_deadline_sheds_without_estimate(self):
        """The one proof that needs no service-time observation: the
        deadline already passed at submit."""
        reason = admission_decision(self.NOW - 0.5, self.NOW, 0, 8, None)
        assert reason is not None and "passed" in reason

    def test_unknown_bucket_never_sheds_a_live_deadline(self):
        assert admission_decision(self.NOW + 1e-6, self.NOW, 10 ** 6, 1,
                                  None) is None

    @settings(max_examples=200, deadline=None)
    @given(budget=st.floats(1e-3, 10.0), queued=st.integers(0, 64),
           mb=st.integers(1, 8), lb=st.floats(1e-4, 5.0))
    def test_shed_iff_provably_infeasible(self, budget, queued, mb, lb):
        """Oracle property: with a known lower bound, shed exactly when
        even the fastest-ever batch cadence cannot drain the FIFO ahead
        plus the request itself inside the budget."""
        need = _batches_needed(queued, mb) * lb
        reason = admission_decision(self.NOW + budget, self.NOW, queued,
                                    mb, lb)
        if need > budget:
            assert reason is not None
        else:
            assert reason is None

    @settings(max_examples=100, deadline=None)
    @given(budget=st.floats(1e-3, 10.0), queued=st.integers(0, 64),
           mb=st.integers(1, 8))
    def test_feasible_never_shed_without_proof(self, budget, queued, mb):
        """A live deadline with no observation is always admitted — the
        engine never sheds on a guess."""
        assert admission_decision(self.NOW + budget, self.NOW, queued,
                                  mb, None) is None


class TestChooseBucket:
    NOW = 1000.0

    def test_empty_heads(self):
        assert choose_bucket({}, self.NOW) is None

    def test_aging_beats_deadlines(self):
        """A head older than starve_after_s wins even against a tighter
        deadline elsewhere — the pre-SLO starvation guard survives."""
        heads = {"old": (self.NOW - 5.0, self.NOW + 100.0, 1),
                 "tight": (self.NOW - 0.1, self.NOW + 0.2, 9)}
        assert choose_bucket(heads, self.NOW, starve_after_s=2.0) == "old"

    def test_edf_picks_earliest_deadline(self):
        heads = {"late": (self.NOW, self.NOW + 9.0, 9),
                 "soon": (self.NOW, self.NOW + 1.0, 1)}
        assert choose_bucket(heads, self.NOW) == "soon"

    def test_edf_prefers_feasible_over_earlier_infeasible(self):
        """An earlier-but-already-doomed deadline must not pre-empt a
        feasible one; serving the doomed head first would miss both."""
        est = ServiceEstimator()
        est.observe("doomed", 5.0)   # expected 5s >> its 1s budget
        est.observe("savable", 0.1)
        heads = {"doomed": (self.NOW, self.NOW + 1.0, 1),
                 "savable": (self.NOW, self.NOW + 2.0, 1)}
        assert choose_bucket(heads, self.NOW, estimator=est) == "savable"

    def test_edf_all_infeasible_earliest_goes_first(self):
        est = ServiceEstimator()
        est.observe("a", 50.0)
        est.observe("b", 50.0)
        heads = {"a": (self.NOW, self.NOW + 2.0, 1),
                 "b": (self.NOW, self.NOW + 1.0, 1)}
        assert choose_bucket(heads, self.NOW, estimator=est) == "b"

    def test_deadline_less_traffic_drains_deepest(self):
        heads = {"shallow": (self.NOW, None, 1),
                 "deep": (self.NOW, None, 7)}
        assert choose_bucket(heads, self.NOW) == "deep"

    def test_hottest_scheduler_ignores_deadlines(self):
        heads = {"tight": (self.NOW, self.NOW + 0.1, 1),
                 "deep": (self.NOW, None, 7)}
        assert choose_bucket(heads, self.NOW,
                             scheduler="hottest") == "deep"


class TestAdmissionInEngine:
    def test_expired_deadline_shed_before_any_compute(self):
        calls = []

        def sample_fn(noise, txt, rngs):
            calls.append(1)
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01)
        eng.start()
        with pytest.raises(ShedError):
            eng.submit(GenRequest(request_id=0, txt=_txt(0),
                                  deadline_s=time.time() - 1.0))
        time.sleep(0.05)  # had it been queued, the batcher would serve it
        eng.stop()
        assert calls == []  # shed at the door: zero sampler invocations
        assert eng.metrics()["shed_count"] == 1
        with pytest.raises(TimeoutError):  # and no result record exists
            eng.result(0, timeout=0.01)

    def test_provably_infeasible_shed_via_lower_bound(self):
        def sample_fn(noise, txt, rngs):
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01)
        req = GenRequest(request_id=0, txt=_txt(0),
                         deadline_s=time.time() + 0.5)
        # fastest-ever batch for this bucket takes 10s: a 0.5s budget is
        # provably unmeetable even with an empty queue
        eng.estimator.observe(eng._bucket_key(req), 10.0)
        eng.start()
        with pytest.raises(ShedError):
            eng.submit(req)
        eng.stop()

    def test_feasible_request_admitted_and_served(self):
        def sample_fn(noise, txt, rngs):
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01)
        req = GenRequest(request_id=0, txt=_txt(0),
                         deadline_s=time.time() + 30.0)
        eng.estimator.observe(eng._bucket_key(req), 0.001)
        eng.start()
        eng.submit(req)
        r = eng.result(0, timeout=30)
        eng.stop()
        assert r.deadline_met is True
        assert eng.metrics()["deadlines_met"] == 1

    def test_admission_control_off_never_sheds(self):
        def sample_fn(noise, txt, rngs):
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01, admission_control=False)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0),
                              deadline_s=time.time() - 1.0))
        r = eng.result(0, timeout=30)
        eng.stop()
        assert r.deadline_met is False  # served, late, counted as missed
        assert eng.metrics()["shed_count"] == 0


class TestEDFBeatsHottest:
    """Acceptance gate: on an overload trace with one tight-SLO request
    stuck behind a deep relaxed-SLO bucket, EDF meets strictly more
    deadlines than the legacy hottest-first drain."""

    SERVICE_S = 0.15

    def _run(self, scheduler):
        def factory(latent_shape, steps):
            def fn(noise, txt, rngs):
                time.sleep(self.SERVICE_S)
                return noise
            return fn

        eng = DiffusionEngine(sampler_factory=factory, max_batch=1,
                              max_wait_s=0.0, scheduler=scheduler,
                              starve_after_s=60.0)
        now = time.time()
        # deep hot bucket, relaxed SLOs — feasible under either policy
        for i in range(4):
            eng.submit(GenRequest(request_id=i, txt=_txt(i), steps=2,
                                  latent_shape=(4, 4),
                                  deadline_s=now + 30.0))
        # one tight-SLO request in a shallow bucket: its budget covers
        # ~2 batches, not the 5 it waits behind under hottest-first
        eng.submit(GenRequest(request_id=99, txt=_txt(99), steps=2,
                              latent_shape=(2, 2),
                              deadline_s=now + 2.5 * self.SERVICE_S))
        eng.start()  # backlog drains under the scheduler's order
        for rid in (0, 1, 2, 3, 99):
            eng.result(rid, timeout=60)
        m = eng.metrics()
        eng.stop()
        return m

    def test_edf_meets_strictly_more_deadlines(self):
        hot = self._run("hottest")
        edf = self._run("edf")
        # hottest drains the deep bucket first: the tight request misses
        assert hot["deadlines_missed"] >= 1
        # EDF serves the earliest deadline first: everything lands
        assert edf["deadlines_missed"] == 0
        assert edf["deadlines_met"] > hot["deadlines_met"]


class TestRouter:
    @staticmethod
    def _replica(service_s=0.0, max_batch=1):
        def factory(latent_shape, steps):
            def fn(noise, txt, rngs):
                if service_s:
                    time.sleep(service_s)
                return noise
            return fn

        return DiffusionEngine(sampler_factory=factory,
                               max_batch=max_batch, max_wait_s=0.0)

    def test_needs_a_replica(self):
        with pytest.raises(ValueError):
            Router([])

    def test_balances_across_replicas_by_depth(self):
        router = Router([self._replica(service_s=0.1) for _ in range(2)])
        router.start()
        placed = [router.submit(GenRequest(request_id=i, txt=_txt(i),
                                           latent_shape=(2,)))
                  for i in range(4)]
        for i in range(4):
            router.result(i, timeout=30)
        router.stop()
        # the in-flight ledger spreads a burst over both replicas
        assert set(placed) == {0, 1}

    def test_fleet_wide_shed_only_when_all_refuse(self):
        router = Router([self._replica() for _ in range(2)])
        router.start()
        with pytest.raises(ShedError):
            router.submit(GenRequest(request_id=0, txt=_txt(0),
                                     latent_shape=(2,),
                                     deadline_s=time.time() - 1.0))
        router.stop()
        m = router.metrics()
        assert m["router_shed_count"] == 1
        # both replicas were tried before the fleet-wide shed
        assert m["replica0_shed_count"] + m["replica1_shed_count"] == 2

    def test_failover_requeues_unserved_requests(self):
        """Two replicas, kill one mid-trace: every request still
        resolves (replay on the survivor), at least one was requeued,
        and the dead replica leaves the rotation."""
        router = Router([self._replica(service_s=0.1) for _ in range(2)])
        router.start()
        for i in range(8):
            router.submit(GenRequest(request_id=i, txt=_txt(i),
                                     latent_shape=(2,), seed=i))
        time.sleep(0.05)  # let replica 0 start chewing its share
        router.fail_replica(0)
        results = {i: router.result(i, timeout=60) for i in range(8)}
        assert router.healthy_replicas() == [1]
        m = router.metrics()
        router.stop()
        assert all(r.latents.shape == (2,) for r in results.values())
        assert m["router_requeued"] >= 1

    def test_result_follows_failover_when_waiting(self):
        """A result() call already blocked on the dying replica follows
        the request to the survivor instead of surfacing the dead
        engine's error."""
        router = Router([self._replica(service_s=0.2) for _ in range(2)])
        router.start()
        placed = [router.submit(GenRequest(request_id=i, txt=_txt(i),
                                           latent_shape=(2,)))
                  for i in range(4)]
        victim = placed[-1]
        got = {}

        def waiter():
            got["res"] = router.result(3, timeout=60)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        router.fail_replica(victim)
        t.join(timeout=60)
        for i in range(3):
            router.result(i, timeout=60)
        router.stop()
        assert not t.is_alive()
        assert got["res"].latents.shape == (2,)

    def test_stream_passes_through_router(self):
        def factory(latent_shape, steps, policy=None, reuse_every=None,
                    stream_every=None):
            if stream_every is None:
                return lambda noise, txt, rngs: noise

            def gen_fn(noise, txt, rngs):
                for k in range(2):
                    yield noise + k, None

            return gen_fn

        router = Router([DiffusionEngine(sampler_factory=factory,
                                         latent_shape=(2,), max_batch=1,
                                         max_wait_s=0.0)])
        router.start()
        router.submit(GenRequest(request_id=0, txt=_txt(0),
                                 stream_every=1))
        chunks = list(router.stream(0, timeout=30))
        r = router.result(0, timeout=30)
        router.stop()
        assert len(chunks) == 2
        np.testing.assert_allclose(chunks[-1], r.latents)

    def test_stream_follows_failover(self):
        """REVIEW regression: a stream used to bind to the submit-time
        replica forever, so a consumer blocked on the dying replica
        never saw the chunks the survivor produced.  The consumer must
        follow the request and still receive every chunk."""
        def factory(latent_shape, steps, policy=None, reuse_every=None,
                    stream_every=None):
            if stream_every is None:
                def fn(noise, txt, rngs):
                    time.sleep(0.25)
                    return noise

                return fn

            def gen_fn(noise, txt, rngs):
                for k in range(1, 4):
                    time.sleep(0.02)
                    yield noise + k, None

            return gen_fn

        router = Router([DiffusionEngine(sampler_factory=factory,
                                         latent_shape=(2,), max_batch=1,
                                         max_wait_s=0.0)
                         for _ in range(2)])
        router.start()
        # one slow blocker per replica so the streaming request sits
        # *queued* on its replica when that replica dies
        router.submit(GenRequest(request_id=0, txt=_txt(0)))
        router.submit(GenRequest(request_id=1, txt=_txt(1)))
        victim = router.submit(GenRequest(request_id=2, txt=_txt(2),
                                          stream_every=1))
        got = []

        def consume():
            for c in router.stream(2, timeout=30):
                got.append(c)

        t = threading.Thread(target=consume)
        t.start()
        time.sleep(0.05)  # consumer is now blocked on the victim
        router.fail_replica(victim)
        t.join(timeout=60)
        res = router.result(2, timeout=60)
        for rid in (0, 1):
            router.result(rid, timeout=60)
        router.stop()
        assert not t.is_alive()
        assert len(got) == 3
        np.testing.assert_allclose(got[-1], res.latents)

    def test_forget_releases_ledger_after_timeout(self):
        """REVIEW: a caller that gives up on a result() timeout keeps
        its ledger entry (so a retry still works) and must release it
        with forget() — otherwise the in-flight count stays inflated
        and skews least-loaded routing."""
        router = Router([self._replica(service_s=0.5)])
        router.start()
        idx = router.submit(GenRequest(request_id=0, txt=_txt(0),
                                       latent_shape=(2,)))
        with pytest.raises(TimeoutError):
            router.result(0, timeout=0.01)
        # the entry survives the timeout: result() is retryable
        assert router.depths()[idx] >= 1
        router.forget(0)
        router.forget(0)  # idempotent
        assert router.depths()[idx] == 0
        with pytest.raises(KeyError):
            router.result(0, timeout=1)
        router.stop()

    def test_stop_claims_each_replica_exactly_once(self):
        """REVIEW: stop() used to read _healthy outside the lock, so a
        concurrent fail_replica could stop the same engine twice (or a
        just-downed replica got stopped again with drain=True).  Both
        paths now claim the replica under the lock first."""
        router = Router([self._replica() for _ in range(2)])
        router.start()
        stops = []
        for i, eng in enumerate(router._replicas):
            orig = eng.stop

            def spy(drain=True, _i=i, _orig=orig):
                stops.append((_i, drain))
                _orig(drain=drain)

            eng.stop = spy
        router.stop()
        router.fail_replica(0)  # already claimed: must not stop again
        router.stop()           # idempotent
        assert stops == [(0, True), (1, True)]

    def test_restart_restores_replica_health(self):
        router = Router([self._replica()])
        router.start()
        router.stop()
        assert router.healthy_replicas() == []
        router.start()
        assert router.healthy_replicas() == [0]
        router.submit(GenRequest(request_id=0, txt=_txt(0),
                                 latent_shape=(2,)))
        assert router.result(0, timeout=30).latents.shape == (2,)
        router.stop()


class TestRouterHealthProbes:
    @staticmethod
    def _replica():
        def factory(latent_shape, steps):
            return lambda noise, txt, rngs: noise

        return DiffusionEngine(sampler_factory=factory, max_batch=1,
                               max_wait_s=0.0)

    def test_probe_health_readmits_restarted_replica(self):
        """§17: a downed replica whose engine is healthy again (ops
        restarted it) rejoins the rotation on the next health probe —
        and only then; a still-dead engine stays out."""
        router = Router([self._replica() for _ in range(2)])
        router.start()
        router.fail_replica(0)
        assert router.healthy_replicas() == [1]
        assert router.probe_health() == []  # engine still stopped
        router._replicas[0].start()         # the restart
        assert router.probe_health() == [0]
        assert router.healthy_replicas() == [0, 1]
        assert router.metrics()["router_readmitted"] == 1
        # traffic spreads over the re-admitted replica again
        placed = [router.submit(GenRequest(request_id=i, txt=_txt(i),
                                           latent_shape=(2,)))
                  for i in range(4)]
        for i in range(4):
            router.result(i, timeout=30)
        router.stop()
        assert 0 in placed

    def test_probe_thread_readmits_on_interval(self):
        router = Router([self._replica() for _ in range(2)],
                        probe_interval_s=0.05)
        router.start()
        router.fail_replica(0)
        router._replicas[0].start()
        deadline = time.time() + 5.0
        while (router.healthy_replicas() != [0, 1]
               and time.time() < deadline):
            time.sleep(0.02)
        healthy = router.healthy_replicas()
        router.stop()
        assert healthy == [0, 1]  # the background probe re-admitted it


class TestGuardrailFailover:
    def test_degraded_state_survives_replica_failover(self):
        """§17.2: router replicas share one DegradationLadder, so a
        bucket family degraded on the dying replica is served at its
        degraded rung by the survivor — no second trip, no second NaN
        batch shipped while the survivor rediscovers the bug."""
        import jax.numpy as jnp

        from repro.core.guardrail import DegradationLadder

        ladder = DegradationLadder()

        def factory(latent_shape, steps, policy=None):
            def fn(noise, txt, rngs):
                if policy != "dense":
                    return jnp.full_like(noise, jnp.nan)
                return jnp.zeros_like(noise)
            return fn

        def replica():
            return DiffusionEngine(sampler_factory=factory, max_batch=1,
                                   max_wait_s=0.0, guardrail=ladder)

        router = Router([replica() for _ in range(2)])
        router.start()
        victim = router.submit(GenRequest(request_id=0, txt=_txt(0),
                                          latent_shape=(2,), steps=2))
        r0 = router.result(0, timeout=30)
        assert r0.degraded and np.all(np.isfinite(r0.latents))
        assert ladder.metrics()["degraded_count"] == 1
        router.fail_replica(victim)
        router.submit(GenRequest(request_id=1, txt=_txt(1),
                                 latent_shape=(2,), steps=2))
        r1 = router.result(1, timeout=30)
        router.stop()
        assert r1.degraded and np.all(np.isfinite(r1.latents))
        # the survivor served straight from the shared degraded rung:
        # no new trip was charged
        assert ladder.metrics()["degraded_count"] == 1
