"""Continuous-batching engine tests: per-request RNG threading, mixed
(resolution, steps) traffic from concurrent submitters, bucket purity,
the compiled-sampler LRU, clean drain on stop(), and the PR-7 bugfix
regressions (mixed prompt lengths, errored-result retrievability,
LMEngine argument validation, event-driven linger, chunked
streaming)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.engine import DiffusionEngine, GenRequest, LMEngine


def _txt(val, tokens=1, dim=1):
    return np.full((tokens, dim), float(val), np.float32)


class TestPerRequestRNG:
    def test_seeds_differ_within_one_batch(self):
        """Regression for the seed bug: sample_fn used to receive
        rngs[0], collapsing every request's sampler randomness onto the
        first request's key.  A sampler that depends ONLY on the rng
        argument must now produce different latents for different seeds
        served in the same batch."""
        batches = []

        def sample_fn(noise, txt, rngs):
            batches.append(noise.shape[0])
            assert rngs.shape == (noise.shape[0], 2)  # full key batch
            return jax.vmap(
                lambda k: jax.random.normal(k, noise.shape[1:]))(rngs)

        eng = DiffusionEngine(sample_fn, latent_shape=(4,), max_batch=4,
                              max_wait_s=0.5)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0), seed=0))
        eng.submit(GenRequest(request_id=1, txt=_txt(0), seed=1))
        r0 = eng.result(0, timeout=30)
        r1 = eng.result(1, timeout=30)
        eng.stop()
        assert 2 in batches  # both requests really shared one batch
        assert not np.allclose(r0.latents, r1.latents)

    def test_seed_determinism_across_batches(self):
        def sample_fn(noise, txt, rngs):
            return jax.vmap(
                lambda k: jax.random.normal(k, noise.shape[1:]))(rngs)

        eng = DiffusionEngine(sample_fn, latent_shape=(4,), max_batch=1,
                              max_wait_s=0.01)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0), seed=7))
        a = eng.result(0, timeout=30).latents
        eng.submit(GenRequest(request_id=1, txt=_txt(0), seed=7))
        b = eng.result(1, timeout=30).latents
        eng.stop()
        np.testing.assert_array_equal(a, b)


class TestMixedTrafficConcurrency:
    BUCKETS = (((2, 2, 1), 2), ((4, 4, 1), 3), ((2, 2, 1), 3))

    def test_threads_mixed_shapes_all_complete(self):
        """Multiple submitter threads, heterogeneous (resolution, steps)
        traffic: every request completes, results map back to the right
        request_id, and no sampler invocation ever mixes shapes."""
        served = []

        def factory(latent_shape, steps):
            def fn(noise, txt, rngs):
                # bucket purity: the whole batch matches this bucket
                assert noise.shape[1:] == latent_shape
                assert txt.shape[0] == noise.shape[0] == rngs.shape[0]
                served.append((latent_shape, steps, noise.shape[0]))
                # encode (request marker, steps) into the output
                return (jnp.zeros_like(noise)
                        + txt[:, 0, 0].reshape((-1,) + (1,) * (noise.ndim - 1))
                        + 1000.0 * steps)
            return fn

        eng = DiffusionEngine(sampler_factory=factory, max_batch=4,
                              max_wait_s=0.02)
        eng.start()
        n_threads, per_thread = 4, 8
        expected = {}

        def submit(tid):
            rng = np.random.default_rng(tid)
            for j in range(per_thread):
                rid = tid * 100 + j
                shape, steps = self.BUCKETS[rng.integers(len(self.BUCKETS))]
                expected[rid] = (shape, steps)
                eng.submit(GenRequest(request_id=rid, txt=_txt(rid),
                                      steps=steps, seed=rid,
                                      latent_shape=shape))
                time.sleep(0.001 * int(rng.integers(3)))

        threads = [threading.Thread(target=submit, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        for rid, (shape, steps) in expected.items():
            r = eng.result(rid, timeout=60)
            assert r.latents.shape == shape
            np.testing.assert_allclose(r.latents,
                                       float(rid) + 1000.0 * steps)
        eng.stop()
        assert sum(b for _, _, b in served) == n_threads * per_thread
        # every served batch drew from exactly one bucket (asserted in
        # fn); batching actually happened under concurrent submission
        assert len(served) <= n_threads * per_thread

    def test_hottest_bucket_drains_first(self):
        order = []

        def factory(latent_shape, steps):
            def fn(noise, txt, rngs):
                order.append((latent_shape, noise.shape[0]))
                return noise
            return fn

        eng = DiffusionEngine(sampler_factory=factory, max_batch=8,
                              max_wait_s=0.05)
        # queue before starting: 1 request cold bucket, 3 hot bucket
        eng.submit(GenRequest(request_id=0, txt=_txt(0), steps=2,
                              latent_shape=(2, 2)))
        for i in range(1, 4):
            eng.submit(GenRequest(request_id=i, txt=_txt(i), steps=2,
                                  latent_shape=(4, 4)))
        eng.start()
        for i in range(4):
            eng.result(i, timeout=30)
        eng.stop()
        assert order[0] == ((4, 4), 3)  # deepest queue served first

    def test_cold_bucket_not_starved_by_hot_traffic(self):
        """Aging guard: a lone request in a cold bucket is served within
        ~starve_after_s even while fresh hot-bucket traffic keeps that
        bucket deeper the whole time (pure hottest-first would starve the
        cold request until the hot stream dries up)."""
        def factory(latent_shape, steps):
            def fn(noise, txt, rngs):
                time.sleep(0.02)
                return noise
            return fn

        eng = DiffusionEngine(sampler_factory=factory, max_batch=4,
                              max_wait_s=0.01, starve_after_s=0.2)
        eng.start()
        # warm both shapes so first-call tracing doesn't skew timing
        eng.submit(GenRequest(request_id=9000, txt=_txt(0), steps=2,
                              latent_shape=(4, 4)))
        eng.submit(GenRequest(request_id=9001, txt=_txt(0), steps=2,
                              latent_shape=(2, 2)))
        eng.result(9000, timeout=30)
        eng.result(9001, timeout=30)

        stop_feed = threading.Event()

        def feeder():  # keep the hot bucket continuously refilled
            rid = 1
            while not stop_feed.is_set():
                if eng.pending() < 8:
                    for _ in range(4):
                        eng.submit(GenRequest(request_id=rid, txt=_txt(rid),
                                              steps=2, latent_shape=(4, 4)))
                        rid += 1
                time.sleep(0.005)

        t = threading.Thread(target=feeder)
        t.start()
        try:
            time.sleep(0.1)  # hot traffic flowing
            eng.submit(GenRequest(request_id=0, txt=_txt(0), steps=2,
                                  latent_shape=(2, 2)))
            r = eng.result(0, timeout=3.0)  # << the feeder's lifetime
            assert r.latents.shape == (2, 2)
        finally:
            stop_feed.set()
            t.join()
            eng.stop(drain=False)

    def test_compiled_sampler_lru_bounded_keeps_hottest(self):
        builds = []

        def factory(latent_shape, steps):
            builds.append((latent_shape, steps))
            return lambda noise, txt, rngs: noise

        eng = DiffusionEngine(sampler_factory=factory, max_batch=2,
                              max_wait_s=0.01, max_compiled=2)
        eng.start()
        rid = 0
        # bucket keys carry the policy name, reuse cadence, the
        # dispatch mesh's seq-shard degree (1 = no ring), the text-
        # embedding shape, the streaming cadence (None = monolithic),
        # and the policy's plan token (pattern-artifact version)
        hot = ((2, 2), 2, None, None, 1, (1, 1), None, None)
        for round_ in range(3):
            for shape, steps in ((hot[0], hot[1]), ((4, 4), 2), ((8, 8), 2)):
                eng.submit(GenRequest(request_id=rid, txt=_txt(rid),
                                      steps=steps, latent_shape=shape))
                eng.result(rid, timeout=30)
                rid += 1
            # the hot bucket is touched again right away each round
            eng.submit(GenRequest(request_id=rid, txt=_txt(rid), steps=2,
                                  latent_shape=(2, 2)))
            eng.result(rid, timeout=30)
            rid += 1
            assert len(eng._compiled) <= 2
            assert hot in eng._compiled  # hottest entry survives eviction
        eng.stop()
        assert len(builds) > 3  # eviction forced rebuilds of cold buckets


class TestStopSemantics:
    def test_stop_drains_cleanly(self):
        """stop() serves everything already queued before joining — no
        result is orphaned under the lock."""
        def sample_fn(noise, txt, rngs):
            time.sleep(0.02)
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=2,
                              max_wait_s=0.01)
        eng.start()
        for i in range(6):
            eng.submit(GenRequest(request_id=i, txt=_txt(i), seed=i))
        eng.stop()  # backlog still queued at this point
        assert eng.pending() == 0
        for i in range(6):
            r = eng.result(i, timeout=1.0)  # already resolved, no wait
            assert r.latents.shape == (2,)

    def test_submit_after_stop_raises(self):
        eng = DiffusionEngine(lambda n, t, r: n, latent_shape=(2,))
        eng.start()
        eng.stop()
        with pytest.raises(RuntimeError):
            eng.submit(GenRequest(request_id=0, txt=_txt(0)))

    def test_failed_batch_reports_error_not_hang(self):
        def sample_fn(noise, txt, rngs):
            raise ValueError("boom")

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=2,
                              max_wait_s=0.01)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0)))
        with pytest.raises(RuntimeError, match="boom"):
            eng.result(0, timeout=30)
        eng.stop()


class TestMixedPromptLengths:
    def test_mixed_txt_shapes_do_not_crash_the_batch(self):
        """Regression: two requests with the same latent shape but
        different prompt lengths L used to land in one bucket, and
        ``jnp.stack([r.txt ...])`` failed the whole batch at stack
        time.  The text-embedding shape is bucket identity now, so both
        requests are served (in separate, shape-pure batches)."""
        served_txt_shapes = []

        def sample_fn(noise, txt, rngs):
            served_txt_shapes.append(txt.shape[1:])
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(4,), max_batch=4,
                              max_wait_s=0.2)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0, tokens=2)))
        eng.submit(GenRequest(request_id=1, txt=_txt(1, tokens=3)))
        r0 = eng.result(0, timeout=30)
        r1 = eng.result(1, timeout=30)
        eng.stop()
        assert r0.latents.shape == (4,) and r1.latents.shape == (4,)
        assert sorted(served_txt_shapes) == [(2, 1), (3, 1)]

    def test_same_txt_shape_still_shares_a_batch(self):
        batches = []

        def sample_fn(noise, txt, rngs):
            batches.append(noise.shape[0])
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(4,), max_batch=4,
                              max_wait_s=0.5)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0, tokens=2)))
        eng.submit(GenRequest(request_id=1, txt=_txt(1, tokens=2)))
        eng.result(0, timeout=30)
        eng.result(1, timeout=30)
        eng.stop()
        assert 2 in batches


class TestSubmitValidation:
    def test_rejects_malformed_requests_at_the_door(self):
        """§17 satellite: a malformed field used to stack fine and then
        crash the sampler, failing every batchmate.  Now submit()
        raises ValueError before the request is queued."""
        eng = DiffusionEngine(lambda n, t, r: n, latent_shape=(2,))
        eng.start()
        with pytest.raises(ValueError, match="steps"):
            eng.submit(GenRequest(request_id=0, txt=_txt(0), steps=0))
        with pytest.raises(ValueError, match="steps"):
            eng.submit(GenRequest(request_id=1, txt=_txt(1), steps=-3))
        with pytest.raises(ValueError, match="latent_shape"):
            eng.submit(GenRequest(request_id=2, txt=_txt(2),
                                  latent_shape=(0, 2)))
        with pytest.raises(ValueError, match="latent_shape"):
            eng.submit(GenRequest(request_id=3, txt=_txt(3),
                                  latent_shape=()))
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit(GenRequest(request_id=4, txt=_txt(4),
                                  deadline_s=-5.0))
        with pytest.raises(ValueError, match="reuse_every"):
            eng.submit(GenRequest(request_id=5, txt=_txt(5),
                                  reuse_every=0))
        with pytest.raises(ValueError, match="stream_every"):
            eng.submit(GenRequest(request_id=6, txt=_txt(6),
                                  stream_every=-1))
        assert eng.pending() == 0  # nothing malformed was queued
        eng.stop()


class TestErroredResultRetrievable:
    def test_retry_after_error_sees_original_error_not_timeout(self):
        """Regression: ``result()`` used to *pop* an errored result
        before raising, so a caller that caught the error (or a
        TimeoutError) and retried got a misleading TimeoutError instead
        of the original batch error."""
        def sample_fn(noise, txt, rngs):
            raise ValueError("boom-original")

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0)))
        for _ in range(3):  # every retry sees the original batch error
            with pytest.raises(RuntimeError, match="boom-original"):
                eng.result(0, timeout=30)
        eng.stop()

    def test_errored_result_evicted_after_ttl(self):
        def sample_fn(noise, txt, rngs):
            raise ValueError("boom")

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01, error_ttl_s=0.1)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0)))
        with pytest.raises(RuntimeError, match="boom"):
            eng.result(0, timeout=30)
        eng.stop()
        time.sleep(0.15)
        with pytest.raises(TimeoutError):
            eng.result(0, timeout=0.05)

    def test_error_tombstone_lives_through_its_expiry_instant(
            self, monkeypatch):
        """Regression (§17 satellite): the eviction predicate used to be
        ``exp <= now``, so a result() retry landing exactly at the TTL
        expiry instant evicted the very tombstone it came for and raised
        a spurious TimeoutError instead of the stored batch error."""
        import repro.serving.engine as engine_mod

        def sample_fn(noise, txt, rngs):
            raise ValueError("boom-ttl")

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01, error_ttl_s=60.0)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0)))
        with pytest.raises(RuntimeError, match="boom-ttl"):
            eng.result(0, timeout=30)
        eng.stop()
        # Retry landing *exactly* at the expiry instant: still the error.
        frozen = eng._error_expiry[0]
        monkeypatch.setattr(engine_mod.time, "time", lambda: frozen)
        with pytest.raises(RuntimeError, match="boom-ttl"):
            eng.result(0, timeout=30)
        monkeypatch.undo()
        # Strictly after the instant: evicted, back to TimeoutError.
        eng._error_expiry[0] = time.time() - 0.001
        with pytest.raises(TimeoutError):
            eng.result(0, timeout=0.01)

    def test_result_timeout_is_clamped_nonnegative(self):
        """A result() deadline in the past must raise TimeoutError
        cleanly (the old code handed Condition.wait a negative
        timeout)."""
        eng = DiffusionEngine(lambda n, t, r: n, latent_shape=(2,))
        eng.start()
        with pytest.raises(TimeoutError):
            eng.result(123, timeout=-0.5)
        with pytest.raises(TimeoutError):
            eng.result(123, timeout=0.0)
        eng.stop()


class TestLMEngineValidation:
    def _engine(self, max_len=8):
        V = 5

        def prefill(tokens):
            B, S = tokens.shape
            return jnp.zeros((B, S, V)), {}

        def decode(tok, cache, idx):
            return jnp.zeros((tok.shape[0], 1, V)), cache

        return LMEngine(prefill, decode, max_len=max_len)

    def test_temperature_without_rng_raises(self):
        """Regression: temperature > 0 with the default rng=None used to
        crash inside jax.random.split(None)."""
        eng = self._engine()
        toks = jnp.zeros((1, 2), jnp.int32)
        with pytest.raises(ValueError, match="rng"):
            eng.generate(toks, num_new=2, temperature=0.7)

    def test_temperature_with_rng_works(self):
        eng = self._engine()
        toks = jnp.zeros((1, 2), jnp.int32)
        out = eng.generate(toks, num_new=2, temperature=0.7,
                           rng=jax.random.PRNGKey(0))
        assert out.shape == (1, 2)

    def test_max_len_enforced(self):
        """Regression: max_len was stored but never enforced — prompt +
        num_new could silently exceed the KV-cache allocation."""
        eng = self._engine(max_len=8)
        toks = jnp.zeros((1, 6), jnp.int32)
        with pytest.raises(ValueError, match="max_len"):
            eng.generate(toks, num_new=3)
        assert eng.generate(toks, num_new=2).shape == (1, 2)


class TestEventDrivenLinger:
    def test_linger_does_not_busy_poll(self, monkeypatch):
        """Regression: _take_batch's linger loop busy-polled with
        time.sleep(0.005).  Batch-mate arrival must wake it through the
        condition variable instead — the batcher thread never calls
        time.sleep."""
        sleep_threads = []
        real_sleep = time.sleep

        def spy(seconds):
            sleep_threads.append(threading.current_thread())
            real_sleep(seconds)

        monkeypatch.setattr(time, "sleep", spy)
        batches = []

        def sample_fn(noise, txt, rngs):
            batches.append(noise.shape[0])
            return noise

        eng = DiffusionEngine(sample_fn, latent_shape=(2,), max_batch=2,
                              max_wait_s=1.0)
        eng.start()
        batcher = eng._thread
        eng.submit(GenRequest(request_id=0, txt=_txt(0)))
        real_sleep(0.05)  # batcher is now lingering for a batch-mate
        t0 = time.time()
        eng.submit(GenRequest(request_id=1, txt=_txt(1)))
        eng.result(0, timeout=30)
        eng.result(1, timeout=30)
        waited = time.time() - t0
        eng.stop()
        assert 2 in batches          # the linger really batched them
        assert batcher not in sleep_threads  # and never slept to poll
        # arrival filled the batch => the linger ended well before its
        # 1s budget (event-driven, not deadline-driven)
        assert waited < 0.8


class TestStreamingDelivery:
    @staticmethod
    def _factory(latent_shape, steps, policy=None, reuse_every=None,
                 stream_every=None):
        if stream_every is None:
            return lambda noise, txt, rngs: noise

        def gen_fn(noise, txt, rngs):
            for k in range(1, 4):  # 3 chunks, last one is final
                time.sleep(0.03)
                yield noise + k, {"chunk": k}

        return gen_fn

    def test_stream_yields_chunks_then_result(self):
        eng = DiffusionEngine(sampler_factory=self._factory,
                              latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0), stream_every=1))
        chunks = list(eng.stream(0, timeout=30))
        r = eng.result(0, timeout=30)
        eng.stop()
        assert len(chunks) == 3
        np.testing.assert_allclose(chunks[-1], r.latents)
        assert not np.allclose(chunks[0], chunks[-1])

    def test_ttff_beats_completion(self):
        eng = DiffusionEngine(sampler_factory=self._factory,
                              latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0), stream_every=1))
        r = eng.result(0, timeout=30)
        eng.stop()
        assert 0 <= r.ttff_s < r.walltime_s  # first frame landed early

    def test_stream_terminates_after_result_consumed(self):
        """REVIEW regression: result() popping the record (and the
        partials) used to leave a stream consumer with no termination
        signal — it hung until TimeoutError.  The finished tombstone
        keeps the chunks readable and ends the stream cleanly."""
        eng = DiffusionEngine(sampler_factory=self._factory,
                              latent_shape=(2,), max_batch=1,
                              max_wait_s=0.01)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0), stream_every=1))
        r = eng.result(0, timeout=30)            # consumes the record
        chunks = list(eng.stream(0, timeout=2))  # must not hang
        eng.stop()
        assert len(chunks) == 3
        np.testing.assert_allclose(chunks[-1], r.latents)

    def test_stream_every_requires_capable_factory(self):
        eng = DiffusionEngine(lambda n, t, r: n, latent_shape=(2,))
        eng.start()
        with pytest.raises(ValueError, match="stream_every"):
            eng.submit(GenRequest(request_id=0, txt=_txt(0),
                                  stream_every=2))
        eng.stop()

    def test_degraded_state_survives_streaming_chunk_boundary(self):
        """§17: a NaN chunk mid-stream trips the ladder *before*
        publication — subscribers never see the bad frame — and the
        batch re-serves under the dense rung without re-publishing the
        chunks the first attempt already delivered."""
        def factory(latent_shape, steps, policy=None, reuse_every=None,
                    stream_every=None):
            if stream_every is None:
                return lambda noise, txt, rngs: noise

            def gen_fn(noise, txt, rngs):
                for k in range(1, 4):
                    bad = policy != "dense" and k == 2
                    yield (jnp.full_like(noise, jnp.nan) if bad
                           else noise + k), None

            return gen_fn

        eng = DiffusionEngine(sampler_factory=factory, latent_shape=(2,),
                              max_batch=1, max_wait_s=0.01,
                              guardrail=True)
        eng.start()
        eng.submit(GenRequest(request_id=0, txt=_txt(0), stream_every=1))
        chunks = list(eng.stream(0, timeout=30))
        r = eng.result(0, timeout=30)
        eng.stop()
        assert r.degraded is True
        assert np.all(np.isfinite(r.latents))
        # exactly one copy of each of the 3 chunks, every one finite:
        # chunk 1 came from the tripped attempt (delivered before the
        # NaN), chunks 2-3 from the dense re-serve
        assert len(chunks) == 3
        assert all(np.all(np.isfinite(c)) for c in chunks)
        np.testing.assert_allclose(chunks[-1], r.latents)
        assert eng.metrics()["dense_fallbacks"] == 1


class TestLauncherExit:
    """``python -m repro.launch.serve`` fails loudly: a request that
    errors makes the process exit non-zero instead of logging and
    exiting 0, and a clean run returns its summary."""

    ARGV = ["--smoke", "--shape", "gen_512", "--steps", "2",
            "--requests", "1", "--max-batch", "1", "model.num_layers=1"]

    @pytest.fixture(autouse=True)
    def _isolated(self, tmp_path, monkeypatch):
        from repro.serving import faults

        # An explicit cache directory: the launcher then leaves JAX's
        # compile-cache config of this test process alone.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        yield
        faults.clear_faults()

    def test_clean_run_returns_summary(self):
        from repro.launch import serve

        summary = serve.main(self.ARGV)
        assert summary["completed"] == [0] and not summary["errors"]
        assert not summary["degraded"]
        assert np.isfinite(summary["latents"][0]).all()

    def test_failed_request_exits_nonzero(self):
        from repro.launch import serve

        with pytest.raises(SystemExit) as e:
            serve.main(self.ARGV + ["--inject-faults", "poison:rid=0"])
        assert e.value.code == 1


class TestReuseStats:
    """``--reuse-stats``: each served request reports what the reuse
    path did (mean savings and q/k snap fractions over its attention
    calls) without changing its latents."""

    # Four steps: with a cadence of 2 the decision made at step 2 (past
    # the dense step 0) is re-applied at step 3.
    ARGV = ["--smoke", "--shape", "gen_512", "--steps", "4",
            "--requests", "1", "--max-batch", "1", "model.num_layers=1",
            "ripple.i_min=1", "ripple.i_max=2"]

    @pytest.fixture(autouse=True)
    def _isolated(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    @pytest.mark.parametrize("reuse_every", [1, 2])
    def test_request_reports_snaps_and_keeps_latents(self, reuse_every):
        from repro.launch import serve

        argv = self.ARGV + ["--reuse-every", str(reuse_every)]
        plain = serve.main(argv)
        stats = serve.main(argv + ["--reuse-stats"])
        r = stats["reuse"]["0"]
        assert 0 < r["reuse_q_snap_frac"] <= 1
        assert 0 < r["reuse_k_snap_frac"] <= 1
        assert 0 < r["reuse_savings"] <= 1
        assert 0 <= r["reuse_structural_savings"] <= 1
        assert (r["cache_hits"] > 0) == (reuse_every > 1)
        assert not any(k.startswith("reuse_")
                       for k in plain["reuse"].get("0", {}))
        np.testing.assert_array_equal(plain["latents"][0],
                                      stats["latents"][0])

    def test_refused_under_a_mesh(self):
        from repro.launch import serve

        with pytest.raises(SystemExit) as e:
            serve.main(self.ARGV + ["--reuse-stats", "--mesh", "1x1"])
        assert e.value.code == 2


class TestCompileCachePlacement:
    def test_env_dir_wins_and_config_is_untouched(self, monkeypatch,
                                                  tmp_path):
        from repro.utils import platform

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert platform.place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_dir_in_checkout(self, monkeypatch):
        import os

        from repro.utils import platform

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            got = platform.place_compile_cache()
            assert got == platform.DEFAULT_COMPILE_CACHE
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_compilation_cache")
