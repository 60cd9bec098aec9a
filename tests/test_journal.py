"""Crash-safety tests (DESIGN.md §18): WAL framing + torn-tail
recovery at every byte offset, clean-shutdown-marker semantics under a
frozen clock, checkpoint-store round-trips (including bfloat16 leaves)
and corruption tolerance, decision-state slice/merge inverses, engine
mid-flight resume (bitwise vs the uninterrupted run), and the router's
checkpointed-failover snapshot."""

import json
import os
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import decision_cache
from repro.serving import journal as journal_lib
from repro.serving.engine import DiffusionEngine, GenRequest
from repro.serving.journal import (CheckpointStore, Journal, recover,
                                   request_from_dict, request_to_dict,
                                   scan_records)
from repro.serving.router import Router


def _txt(val, tokens=2, dim=3):
    return np.full((tokens, dim), float(val), np.float32)


def _req(rid, **kw):
    kw.setdefault("txt", _txt(rid))
    kw.setdefault("latent_shape", (4,))
    return GenRequest(request_id=rid, **kw)


# ---------------------------------------------------------------------------
# WAL framing
# ---------------------------------------------------------------------------


class TestFraming:
    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 12), rid0=st.integers(0, 999))
    def test_append_scan_round_trip(self, n, rid0):
        """Property: any sequence of lifecycle appends scans back
        intact, in order, with contiguous sequence numbers.  Each
        example gets its own directory (a function-scoped ``tmp_path``
        would be shared by every example)."""
        with tempfile.TemporaryDirectory() as tmp:
            d = os.path.join(tmp, "journal")
            j = Journal(d, fsync="never")
            events = []
            for i in range(n):
                ev = ("submitted", "chunk", "finished", "shed")[i % 4]
                j.append(ev, rid0 + i, i=i)
                events.append((ev, rid0 + i))
            j.close(clean=False)
            records, torn = scan_records(os.path.join(d, "journal.log"))
            assert not torn
            assert [(r["ev"], r["rid"]) for r in records] == events
            assert [r["seq"] for r in records] == list(range(1, n + 1))

    def test_fsync_policies(self, tmp_path):
        for policy in ("always", "interval", "never"):
            d = str(tmp_path / policy)
            j = Journal(d, fsync=policy, fsync_interval=2)
            for i in range(5):
                j.append("chunk", i)
            m = j.metrics()
            j.close(clean=False)
            if policy == "always":
                assert m["journal_fsyncs"] == 5
            elif policy == "interval":
                assert m["journal_fsyncs"] == 2  # after appends 2 and 4
            else:
                assert m["journal_fsyncs"] == 0
        with pytest.raises(ValueError):
            Journal(str(tmp_path / "bad"), fsync="sometimes")

    def test_torn_tail_at_every_byte_offset(self, tmp_path):
        """Truncating anywhere inside the final frame loses exactly
        that record: every prior record survives, torn is flagged."""
        d = str(tmp_path / "torn")
        j = Journal(d, fsync="never")
        for i in range(3):
            j.append("chunk", i, pad="x" * (10 + 7 * i))
        j.close(clean=False)
        path = os.path.join(d, "journal.log")
        with open(path, "rb") as f:
            data = f.read()
        # Frame offsets from the headers themselves.
        offs, off = [], 0
        while off < len(data):
            (length,) = np.frombuffer(data[off:off + 4], np.uint32)
            offs.append(off)
            off += 8 + int(length)
        last = offs[-1]
        for cut in range(last, len(data)):
            with open(path, "wb") as f:
                f.write(data[:cut])
            records, torn = scan_records(path)
            assert len(records) == 2
            assert torn == (cut > last)
        with open(path, "wb") as f:
            f.write(data)
        records, torn = scan_records(path)
        assert len(records) == 3 and not torn

    def test_corrupt_middle_record_stops_scan(self, tmp_path):
        d = str(tmp_path / "mid")
        j = Journal(d, fsync="never")
        for i in range(3):
            j.append("chunk", i)
        j.close(clean=False)
        path = os.path.join(d, "journal.log")
        with open(path, "rb") as f:
            data = bytearray(f.read())
        data[len(data) // 2] ^= 0xFF  # flip a bit mid-file
        with open(path, "wb") as f:
            f.write(bytes(data))
        records, torn = scan_records(path)
        assert torn and len(records) < 3

    def test_reopen_truncates_torn_tail_and_continues_seq(self, tmp_path):
        d = str(tmp_path / "reopen")
        j = Journal(d, fsync="never")
        j.append("submitted", 1)
        j.append("chunk", 1)
        j.close(clean=False)
        path = os.path.join(d, "journal.log")
        with open(path, "ab") as f:
            f.write(b"\x99\x00\x00\x00garbage")  # torn partial frame
        j2 = Journal(d, fsync="never")
        seq = j2.append("finished", 1)
        j2.close(clean=False)
        assert seq == 3
        records, torn = scan_records(path)
        assert not torn
        assert [r["ev"] for r in records] == ["submitted", "chunk",
                                              "finished"]


# ---------------------------------------------------------------------------
# Clean-shutdown marker (frozen clock)
# ---------------------------------------------------------------------------


class TestCleanMarker:
    def test_clean_close_vs_crash(self, tmp_path):
        clock = [1234.5]
        d = str(tmp_path / "clean")
        j = Journal(d, time_fn=lambda: clock[0])
        j.append("submitted", 7)
        j.append("finished", 7)
        j.close(clean=True)
        with open(os.path.join(d, "CLEAN"), encoding="utf-8") as f:
            marker = json.load(f)
        assert marker == {"last_seq": 2, "time": 1234.5}
        assert recover(d).clean

        # Opening removes the marker: a running process is not a clean
        # snapshot.  A crash (no close) must then scan as unclean.
        j2 = Journal(d, time_fn=lambda: clock[0])
        assert not os.path.exists(os.path.join(d, "CLEAN"))
        j2.append("submitted", 8)
        del j2  # crash: no close, no marker
        rec = recover(d)
        assert not rec.clean
        assert list(rec.pending) == [8]

    def test_stale_marker_is_a_crash(self, tmp_path):
        """A marker from an older clean run followed by more journal
        records must not mask the later crash."""
        d = str(tmp_path / "stale")
        j = Journal(d)
        j.append("submitted", 1)
        j.close(clean=True)
        # Re-plant the stale marker after more records land.
        with open(os.path.join(d, "CLEAN"), encoding="utf-8") as f:
            stale = f.read()
        j2 = Journal(d)
        j2.append("submitted", 2)
        j2._f.close()  # simulate crash without close()
        with open(os.path.join(d, "CLEAN"), "w", encoding="utf-8") as f:
            f.write(stale)
        rec = recover(d)
        assert not rec.clean

    def test_empty_directory_is_clean(self, tmp_path):
        rec = recover(str(tmp_path / "nothing"))
        assert rec.clean and not rec.pending and rec.events == 0


# ---------------------------------------------------------------------------
# Recovery fold + request round-trip
# ---------------------------------------------------------------------------


class TestRecovery:
    def test_event_order_fold(self, tmp_path):
        d = str(tmp_path / "fold")
        j = Journal(d)
        reqs = {i: _req(i, steps=4, stream_every=2, policy="ripple",
                        seed=i) for i in range(4)}
        for r in reqs.values():
            j.record_submitted(r)
        j.record_chunk(0, 0, step=2)
        j.record_chunk(1, 0, step=2)
        j.record_chunk(1, 1, step=4)
        j.record_finished(1)
        j.record_finished(2, error="poisoned")
        j.record_shed(3, "deadline passed")
        j.close(clean=False)
        rec = recover(d)
        assert sorted(rec.pending) == [0]
        assert rec.finished == {1: None, 2: "poisoned"}
        assert rec.shed == {3: "deadline passed"}
        assert rec.chunks[0] == {"chunk": 0, "step": 2}
        assert rec.chunks[1] == {"chunk": 1, "step": 4}
        back = request_from_dict(rec.pending[0])
        assert back.request_id == 0 and back.steps == 4
        assert back.stream_every == 2 and back.policy == "ripple"
        np.testing.assert_array_equal(back.txt, reqs[0].txt)

    def test_request_round_trip_excludes_runtime_fields(self):
        r = _req(5, steps=6, seed=9, guidance=2.5, reuse_every=3,
                 deadline_s=123.4, stream_every=2)
        r.resume = {"step": 2, "x": np.zeros(4)}
        r.recovered = True
        d = request_to_dict(r)
        assert "resume" not in json.dumps({k: v for k, v in d.items()
                                           if k != "txt"})
        back = request_from_dict(json.loads(json.dumps(d)))
        assert back.resume is None and not back.recovered
        for field in ("request_id", "steps", "seed", "guidance",
                      "latent_shape", "reuse_every", "deadline_s",
                      "stream_every"):
            assert getattr(back, field) == getattr(r, field), field
        np.testing.assert_array_equal(back.txt, r.txt)


# ---------------------------------------------------------------------------
# Checkpoint store
# ---------------------------------------------------------------------------


class TestCheckpointStore:
    def test_round_trip_with_bfloat16_dstate(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        x = np.arange(24, dtype=np.float32).reshape(2, 3, 4) / 7.0
        dstate = {
            "hits": np.ones((3, 1, 2), np.int32),
            "bias": np.asarray(jnp.full((3, 1, 2, 2), 0.5,
                                        jnp.bfloat16)),
            "block_map": None,
        }
        store.put(3, step=2, x=x, seed=11, bucket=((4,), 4, None),
                  dstate=dstate)
        ck = store.get(3)
        assert ck["step"] == 2 and ck["seed"] == 11
        assert ck["bucket"] == ((4,), 4, None)
        np.testing.assert_array_equal(ck["x"], x)
        assert ck["dstate"]["block_map"] is None
        np.testing.assert_array_equal(ck["dstate"]["hits"],
                                      dstate["hits"])
        assert ck["dstate"]["bias"].dtype == dstate["bias"].dtype
        np.testing.assert_array_equal(
            np.asarray(ck["dstate"]["bias"], np.float32),
            np.asarray(dstate["bias"], np.float32))

    def test_corrupt_checkpoint_degrades_to_none(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        store.put(1, step=1, x=np.zeros(4, np.float32), seed=0)
        path = store._path(1)
        with open(path, "r+b") as f:
            f.seek(-3, os.SEEK_END)
            f.write(b"\xff\xff\xff")
        assert store.get(1) is None  # body CRC mismatch
        with open(path, "wb") as f:
            f.write(b"\x01")
        assert store.get(1) is None  # truncated header
        assert store.get(99) is None  # absent

    def test_bounded_eviction_and_discard(self, tmp_path):
        store = CheckpointStore(str(tmp_path), max_entries=2)
        for rid in range(4):
            store.put(rid, step=1, x=np.zeros(2, np.float32), seed=rid)
        assert store.count() == 2
        assert store.rids() == [2, 3]  # least-recently-written evicted
        assert store.get(0) is None
        assert not os.path.exists(store._path(0))
        store.discard(3)
        store.discard(3)  # idempotent
        assert store.rids() == [2]
        # Overwrite moves a rid to most-recently-written.
        store.put(4, step=1, x=np.zeros(2, np.float32), seed=4)
        store.put(2, step=2, x=np.zeros(2, np.float32), seed=2)
        store.put(5, step=1, x=np.zeros(2, np.float32), seed=5)
        assert store.rids() == [2, 5]

    def test_restart_re_adopts_existing_files(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        for rid in (10, 20):
            store.put(rid, step=3, x=np.full(2, rid, np.float32), seed=0)
        again = CheckpointStore(str(tmp_path), max_entries=8)
        assert sorted(again.rids()) == [10, 20]
        assert again.get(20)["x"][0] == 20.0


# ---------------------------------------------------------------------------
# Decision-state (de)serialization
# ---------------------------------------------------------------------------


class TestDecisionState:
    def _batched_state(self, batch=3):
        # layer-stacked (L, B, ...) leaves, the engine's checkpoint shape
        return decision_cache.CachedDecision(
            hits=jnp.arange(2 * batch, dtype=jnp.int32).reshape(2, batch),
            refreshes=jnp.ones((2, batch), jnp.int32),
            bias=jnp.full((2, batch, 2, 2), 0.25, jnp.bfloat16),
            ref_stat=jnp.zeros((2, batch), jnp.float32))

    def test_slice_merge_inverse(self):
        state = self._batched_state(3)
        parts = [decision_cache.slice_state(state, i) for i in range(3)]
        back = decision_cache.merge_states(parts)
        for name in ("hits", "refreshes", "bias", "ref_stat"):
            np.testing.assert_array_equal(np.asarray(getattr(back, name)),
                                          np.asarray(getattr(state, name)))
        assert back.block_map is None

    def test_arrays_round_trip(self):
        state = self._batched_state(2)
        arrays = decision_cache.state_to_arrays(state)
        assert arrays["block_map"] is None
        back = decision_cache.state_from_arrays(arrays)
        np.testing.assert_array_equal(np.asarray(back.bias),
                                      np.asarray(state.bias))
        with pytest.raises(ValueError):
            decision_cache.state_from_arrays({"not_a_field": None})

    def test_mixed_none_merge_rejected(self):
        a = decision_cache.CachedDecision(hits=jnp.ones((1, 1), jnp.int32))
        b = decision_cache.CachedDecision()
        with pytest.raises(ValueError):
            decision_cache.merge_states([a, b])

    def test_sharded_state_not_sliceable(self):
        state = decision_cache.CachedDecision(
            hits=jnp.ones((1, 2), jnp.int32),
            elided=jnp.zeros((1,), jnp.int32))
        with pytest.raises(ValueError):
            decision_cache.slice_state(state, 0)


# ---------------------------------------------------------------------------
# Engine resume (fake resume-capable streaming sampler)
# ---------------------------------------------------------------------------

STEPS = 4


def _counting_factory(delay_s=0.0):
    """Sampler factory honouring the §18 resume contract: x gains +1
    per step from the checkpointed offset, so any trajectory is
    predictable and resume-vs-monolithic is exactly comparable."""

    def factory(latent_shape, steps, policy=None, reuse_every=None,
                stream_every=None):
        def fn(noise, txt, rngs, resume=None):
            start = 0 if resume is None else int(resume["step"])

            def gen():
                cur = jnp.asarray(noise)
                for s in range(start, steps):
                    if delay_s:
                        time.sleep(delay_s)
                    cur = cur + 1.0
                    yield cur, {"__ckpt__": {"step": s + 1,
                                             "dstate": None}}
            return gen()
        return fn
    return factory


class TestEngineResume:
    def _engine(self, tmp_path, name, delay_s=0.0):
        journal = Journal(str(tmp_path / name))
        store = CheckpointStore(str(tmp_path / name))
        eng = DiffusionEngine(sampler_factory=_counting_factory(delay_s),
                              latent_shape=(4,), max_batch=2,
                              max_wait_s=0.05, journal=journal,
                              checkpoint_store=store)
        return eng, journal, store

    def test_resume_bitwise_equals_uninterrupted(self, tmp_path):
        eng, journal, store = self._engine(tmp_path, "bitwise")
        eng.start()
        eng.submit(_req(0, steps=STEPS, stream_every=1, seed=3))
        chunks = list(eng.stream(0, timeout=30))
        full = eng.result(0, timeout=30)
        assert len(chunks) == STEPS
        # Resume a twin from the step-2 state, as a restart would.
        eng.submit(_req(1, steps=STEPS, stream_every=1, seed=3,
                        resume={"step": 2, "x": chunks[1],
                                "dstate": None}))
        resumed = eng.result(1, timeout=30)
        m = eng.metrics()
        eng.stop()
        journal.close()
        np.testing.assert_array_equal(resumed.latents, full.latents)
        assert m["resumed_count"] == 1
        assert m["last_resume_step"] == 2

    def test_journal_and_checkpoint_lifecycle(self, tmp_path):
        eng, journal, store = self._engine(tmp_path, "lifecycle")
        eng.start()
        eng.submit(_req(0, steps=STEPS, stream_every=1, seed=0))
        eng.result(0, timeout=30)
        eng.stop()
        journal.close(clean=True)
        rec = recover(str(tmp_path / "lifecycle"))
        assert rec.clean and not rec.pending
        assert rec.finished == {0: None}
        assert rec.chunks[0]["step"] == STEPS
        assert store.count() == 0  # discarded at finish
        assert store.metrics()["checkpoint_writes"] == STEPS - 1

    def test_recovered_request_counts(self, tmp_path):
        eng, journal, _ = self._engine(tmp_path, "recovered")
        eng.start()
        req = _req(0, steps=STEPS, stream_every=1)
        req.recovered = True
        eng.submit(req)
        eng.result(0, timeout=30)
        m = eng.metrics()
        eng.stop()
        journal.close()
        assert m["recovered_count"] == 1

    def test_invalid_resume_payload_rejected(self, tmp_path):
        eng, journal, _ = self._engine(tmp_path, "invalid")
        eng.start()
        for resume in ({"step": 1},                       # missing x
                       {"step": -1, "x": np.zeros(4)},    # bad step
                       {"step": STEPS, "x": np.zeros(4)},  # >= steps
                       {"step": 1, "x": np.zeros(4)}):    # off-boundary
            with pytest.raises(ValueError):
                eng.submit(_req(9, steps=STEPS, stream_every=2,
                                resume=resume))
        eng.stop()
        journal.close()

    def test_replay_fallback_without_resume_support(self, tmp_path):
        """A factory sampler without a resume kwarg still serves a
        checkpointed request — by deterministic replay from step 0."""
        def factory(latent_shape, steps, policy=None, reuse_every=None,
                    stream_every=None):
            def fn(noise, txt, rngs):
                return jnp.asarray(noise) + float(steps)
            return fn

        eng = DiffusionEngine(sampler_factory=factory, latent_shape=(4,),
                              max_batch=1, max_wait_s=0.01)
        eng.start()
        x = np.full((4,), 5.0, np.float32)
        eng.submit(_req(0, steps=STEPS, stream_every=2,
                        resume={"step": 2, "x": x, "dstate": None}))
        res = eng.result(0, timeout=30)
        eng.stop()
        assert res.error is None and res.latents.shape[-1] == 4


# ---------------------------------------------------------------------------
# Real vdit sampler: resume is bitwise-equal to the monolithic run
# ---------------------------------------------------------------------------


class TestRealSamplerResume:
    def test_build_sampler_resume_bitwise(self):
        """The §18 claim on the real model: restarting the streaming
        vdit sampler from a chunk-boundary checkpoint ``(x, dstate,
        step)`` reproduces the uninterrupted final latents bitwise —
        the PR 7 chunk-chaining exactness carries over to resume."""
        import dataclasses

        from repro.configs import get_smoke_config
        from repro.launch.serve import build_sampler
        from repro.launch.workloads import model_fns
        from repro.models.params import init_params

        arch = get_smoke_config("vdit-paper")
        sp = dataclasses.replace(
            [s for s in arch.shapes if s.kind == "generate"][0],
            img_res=32, steps=4)
        params = init_params(model_fns(arch), jax.random.PRNGKey(0))
        fn, lshape = build_sampler(arch, sp, params, stream_every=2,
                                   reuse_every=2)
        m = arch.model
        noise = jax.random.normal(jax.random.PRNGKey(3), (1, *lshape))
        txt = 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                       (1, m.txt_tokens, m.txt_dim))
        rngs = jnp.stack([jax.random.PRNGKey(7)])

        chunks = []
        for lat, aux in fn(noise, txt, rngs):
            chunks.append((np.asarray(lat), aux.pop("__ckpt__", None)))
        assert len(chunks) == 2  # 4 steps at K=2
        full = chunks[-1][0]
        mid_lat, mid_ck = chunks[0]
        assert mid_ck is not None and mid_ck["step"] == 2

        resumed = list(fn(jnp.asarray(mid_lat), txt, rngs,
                          resume={"step": 2,
                                  "dstate": mid_ck["dstate"]}))
        assert len(resumed) == 1  # only the remaining chunk
        np.testing.assert_array_equal(np.asarray(resumed[-1][0]), full)


# ---------------------------------------------------------------------------
# Router checkpointed failover
# ---------------------------------------------------------------------------


class TestRouterCheckpointedFailover:
    def test_with_checkpoint_snapshot_rules(self, tmp_path):
        store = CheckpointStore(str(tmp_path))
        eng = DiffusionEngine(sampler_factory=_counting_factory(),
                              latent_shape=(4,))
        router = Router([eng], checkpoint_store=store)
        x = np.zeros(4, np.float32)

        req = _req(1, steps=8, stream_every=2)
        assert router._with_checkpoint(req) is req  # no checkpoint yet
        store.put(1, step=4, x=x, seed=0)
        out = router._with_checkpoint(req)
        assert out is not req and out.resume["step"] == 4

        store.put(2, step=3, x=x, seed=0)   # not a chunk boundary
        assert router._with_checkpoint(
            _req(2, steps=8, stream_every=2)).resume is None
        store.put(3, step=8, x=x, seed=0)   # final step: nothing left
        assert router._with_checkpoint(
            _req(3, steps=8, stream_every=2)).resume is None
        store.put(4, step=2, x=x, seed=0)   # older than current resume
        stale = _req(4, steps=8, stream_every=2,
                     resume={"step": 4, "x": x, "dstate": None})
        assert router._with_checkpoint(stale).resume["step"] == 4
        assert router._with_checkpoint(
            _req(5, steps=8)) .resume is None  # no streaming cadence

    def test_failover_resumes_from_checkpoint(self, tmp_path):
        """Lose the replica serving a checkpointed request to its hang
        watchdog (the §17.4 path that really strands mid-flight work —
        an in-process ``stop`` lets the batch finish): the survivor
        must resume past the checkpoint (not replay from 0), the stream
        must stay one contiguous chunk sequence, and the final latents
        must match the uninterrupted trajectory."""
        store = CheckpointStore(str(tmp_path))
        # Replica 0 checkpoints two chunks (0.2s apart) and then hangs
        # past its 0.5s watchdog budget; replica 1 is instant.
        slow = DiffusionEngine(sampler_factory=_counting_factory(0.2),
                               latent_shape=(4,), max_batch=1,
                               max_wait_s=0.01, checkpoint_store=store,
                               batch_timeout_s=0.5)
        fast = DiffusionEngine(sampler_factory=_counting_factory(),
                               latent_shape=(4,), max_batch=1,
                               max_wait_s=0.01, checkpoint_store=store)
        router = Router([slow, fast], checkpoint_store=store)
        router.start()
        rid = 0
        router.submit(_req(rid, steps=STEPS, stream_every=1, seed=1))
        chunks = [np.asarray(c)
                  for c in router.stream(rid, timeout=30)]
        res = router.result(rid, timeout=30)
        # Uninterrupted twin, same seed, on the healthy replica: the
        # resumed trajectory applies the identical op sequence, so the
        # final latents must match bitwise.
        router.submit(_req(1, steps=STEPS, stream_every=1, seed=1))
        twin = router.result(1, timeout=30)
        m = router.metrics()
        router.stop()
        assert res.error is None
        assert m["router_requeued"] >= 1
        assert m["router_resumed"] >= 1
        assert m["router_resumed_from_step"] >= 1
        np.testing.assert_array_equal(res.latents, twin.latents)
        # Contiguous chunk trajectory across the failover: chunk i is
        # the step-(i+1) state (float32 rounding aside), the last one
        # is the final latents.
        assert len(chunks) == STEPS
        for a, b in zip(chunks, chunks[1:]):
            np.testing.assert_allclose(b - a, np.ones(4, np.float32),
                                       rtol=1e-6)
        np.testing.assert_array_equal(chunks[-1], res.latents)
