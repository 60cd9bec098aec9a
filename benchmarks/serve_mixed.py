"""Mixed-shape serving benchmark — continuous batching, SLO scheduling,
streaming TTFF, and the multi-replica router (DESIGN.md §10.4, §15).

Three sections, all on the miniature vDiT over a deterministic
round-robin stream across three (resolution, steps) buckets:

1. **bucketed vs single** — the bucketed continuous-batching engine vs
   the seed-style one-engine-per-shape baseline: mixed traffic needs no
   per-shape engines.
2. **scheduler policies** — the same deadline-stamped overload trace
   served under ``hottest`` (pre-SLO drain order) and ``edf``
   (deadline-aware, DESIGN.md §15.1).  Requests stream chunked latents
   (``--stream-every``), so **time-to-first-frame** is measured per
   request next to completion latency; one probe request carries an
   already-expired deadline so admission control provably sheds it at
   the door (§15.2) and the shed path stays exercised.
3. **router** (``--router-replicas N``) — the front-door router over N
   engine replicas (§15.4) on the same deadline-stamped trace.

Both engines in section 1 are warmed with one full pass (compiles
excluded), then timed in steady state; sections 2–3 warm the same way,
which also seeds the admission estimator.  CPU wall time is relative
only (one serial device serves every bucket).

Reported rows (CSV: name,us_per_call,derived):
  serve_mixed[bucketed_p50/p95]  — per-request latency percentiles (us)
  serve_mixed[single_p50/p95]    — same for the sequential baseline
  serve_mixed[speedup]           — stream walltime ratio (baseline /
                                   bucketed); derived = bucketed stream
                                   walltime in seconds
  serve_mixed[hottest_p50/p95]   — scheduler-policy latency (us);
  serve_mixed[edf_p50/p95]         derived = ttff_ms=..;shed_count=..;
                                   met=..;missed=.. for that policy
  serve_mixed[router_p50/p95]    — router fleet latency (us); derived
                                   adds replicas=..;requeued=..
  serve_mixed[guard_off_p50/p95] — sentinel-off vs sentinel-on latency
  serve_mixed[guard_on_p50/p95]    (us); derived = overhead_pct=..
  serve_mixed[guardrail_overhead]— p50 overhead percent (DESIGN.md §17)
  serve_mixed[journal_off_p50/95]— durability-off vs durability-on
  serve_mixed[journal_on_p50/95]   latency (us); derived = overhead_pct
  serve_mixed[journal_overhead]  — p50 overhead percent of the request
                                   journal + chunk checkpoints
                                   (DESIGN.md §18; acceptance bar <5%)
  serve_mixed[chaos_completed]   — chaos drill only (``--inject-faults``
                                   or ``$REPRO_FAULTS``): completions;
                                   derived = degraded/failover counters
                                   plus resumed=..;resumed_from_step=..
                                   (checkpointed failover, §18).
                                   The ``--json`` record then carries a
                                   full ``chaos`` object.
  serve_mixed[crash_recovered]   — ``crash`` fault only: the in-process
                                   restart drill (journaled traffic, a
                                   no-drain no-marker teardown once a
                                   chunk checkpoint lands, then recovery
                                   + mid-flight resume into a fresh
                                   engine); derived = resumed_from_step.

``--json PATH`` additionally writes a BENCH-style record of the rows
(the same schema ``benchmarks/run.py`` emits), so CI can assert the
TTFF and shed fields without scraping stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.serve import build_sampler, make_sampler_factory
from repro.launch.workloads import (mixed_gen_shapes, mixed_request_stream,
                                    model_fns)
from repro.models.params import init_params

REQUESTS = 9


def _drive(engine, traffic):
    """Submit the whole stream to a *started* engine, wait for every
    result; returns (per-request latencies, stream walltime).
    Run once to warm (compiles) and once to measure steady state — the
    deterministic stream reproduces the same batch shapes, so the timed
    pass never compiles."""
    t0 = time.time()
    submit_t = {}
    for _, req in traffic:
        submit_t[req.request_id] = time.time()
        engine.submit(req)
    lat = []
    for _, req in traffic:
        engine.result(req.request_id, timeout=600)
        lat.append(time.time() - submit_t[req.request_id])
    wall = time.time() - t0
    return np.asarray(lat), wall


def _drive_slo(front, traffic, deadline_ms, *, shed_probe=None):
    """Deadline-stamped pass: every request gets ``now + deadline_ms``
    at submit; ``shed_probe`` (a spare GenRequest) is submitted with an
    already-expired deadline so admission provably sheds it.  Returns
    (latencies, ttffs, met, missed, shed)."""
    from repro.serving.slo import ShedError

    shed = 0
    if shed_probe is not None:
        shed_probe.deadline_s = time.time() - 1.0
        try:
            front.submit(shed_probe)
        except ShedError:
            shed += 1
    submit_t = {}
    for _, req in traffic:
        req.deadline_s = time.time() + deadline_ms / 1e3
        submit_t[req.request_id] = time.time()
        front.submit(req)
    lat, ttff, met, missed = [], [], 0, 0
    for _, req in traffic:
        r = front.result(req.request_id, timeout=600)
        lat.append(time.time() - submit_t[req.request_id])
        ttff.append(r.ttff_s)
        if r.deadline_met:
            met += 1
        else:
            missed += 1
    return np.asarray(lat), np.asarray(ttff), met, missed, shed


def _policy_rows(tag, lat, ttff, met, missed, shed, extra=""):
    derived = (f"ttff_ms={np.percentile(ttff, 50) * 1e3:.1f};"
               f"shed_count={shed};met={met};missed={missed}{extra}")
    return [f"serve_mixed[{tag}_p50],{np.percentile(lat, 50) * 1e6:.0f},"
            f"{derived}",
            f"serve_mixed[{tag}_p95],{np.percentile(lat, 95) * 1e6:.0f},"
            f"{derived}"]


def _bucketed_vs_single(arch, shapes, params, traffic, rows):
    from repro.serving.engine import DiffusionEngine

    # Bucketed continuous batching: one engine, one queue, all shapes.
    factory, plan_fn = make_sampler_factory(arch, shapes, params)
    eng = DiffusionEngine(sampler_factory=factory, plan_fn=plan_fn,
                          max_batch=4, max_wait_s=0.02)
    eng.start()
    _drive(eng, traffic)  # warm: compiles every bucket's sampler
    b_lat, b_wall = _drive(eng, traffic)
    eng.stop()

    # Seed-style baseline: one single-shape engine per bucket, shapes
    # served sequentially (requests still batch within their own shape).
    s_lat_all, s_wall = [], 0.0
    for sp in shapes:
        fn, lat_shape = build_sampler(arch, sp, params)
        sub = [(s, r) for s, r in traffic if s.name == sp.name]
        single = DiffusionEngine(fn, lat_shape, max_batch=4, max_wait_s=0.02)
        single.start()
        _drive(single, sub)  # warm
        lat, wall = _drive(single, sub)
        single.stop()
        s_lat_all.append(lat)
        s_wall += wall
    s_lat = np.concatenate(s_lat_all)

    rows += [
        f"serve_mixed[bucketed_p50],{np.percentile(b_lat, 50) * 1e6:.0f},",
        f"serve_mixed[bucketed_p95],{np.percentile(b_lat, 95) * 1e6:.0f},",
        f"serve_mixed[single_p50],{np.percentile(s_lat, 50) * 1e6:.0f},",
        f"serve_mixed[single_p95],{np.percentile(s_lat, 95) * 1e6:.0f},",
        f"serve_mixed[speedup],{s_wall / max(b_wall, 1e-9):.2f},"
        f"{b_wall:.2f}",
    ]


def _scheduler_section(arch, shapes, params, args, rows):
    from repro.serving.engine import DiffusionEngine

    factory, _ = make_sampler_factory(arch, shapes, params)
    for sched in ("hottest", "edf"):
        traffic = mixed_request_stream(arch, shapes, args.requests,
                                       stream_every=args.stream_every)
        probe = mixed_request_stream(arch, shapes, 1, seed=777,
                                     stream_every=args.stream_every)[0][1]
        probe.request_id = 10_000
        eng = DiffusionEngine(sampler_factory=factory, max_batch=4,
                              max_wait_s=0.02, scheduler=sched)
        eng.start()
        _drive(eng, traffic)  # warm compiles + seeds the estimator
        lat, ttff, met, missed, shed = _drive_slo(
            eng, traffic, args.deadline_ms, shed_probe=probe)
        eng.stop()
        rows += _policy_rows(sched, lat, ttff, met, missed, shed)


def _router_section(arch, shapes, params, args, rows):
    from repro.serving.engine import DiffusionEngine
    from repro.serving.router import Router

    factory, _ = make_sampler_factory(arch, shapes, params)
    router = Router([
        DiffusionEngine(sampler_factory=factory, max_batch=4,
                        max_wait_s=0.02)
        for _ in range(args.router_replicas)])
    router.start()
    traffic = mixed_request_stream(arch, shapes, args.requests,
                                   stream_every=args.stream_every)
    # two warm passes so every replica the balancer touches has
    # compiled samplers before the timed pass
    _drive(router, traffic)
    _drive(router, traffic)
    probe = mixed_request_stream(arch, shapes, 1, seed=778,
                                 stream_every=args.stream_every)[0][1]
    probe.request_id = 10_001
    lat, ttff, met, missed, shed = _drive_slo(
        router, traffic, args.deadline_ms, shed_probe=probe)
    m = router.metrics()
    router.stop()
    # ``shed`` (the probe, counted at submit) already equals the
    # router's fleet-wide shed counter — don't double-count it.
    rows += _policy_rows(
        "router", lat, ttff, met, missed, shed,
        extra=f";replicas={args.router_replicas};"
              f"requeued={m['router_requeued']}")


def _guardrail_section(arch, shapes, params, traffic, rows):
    """Sentinel overhead (DESIGN.md §17): the same steady-state stream
    with the in-graph guardrail sentinels off vs on.  The acceptance bar
    is <2% on p50 — the sentinels are elementwise passes next to the
    attention math."""
    from repro.serving.engine import DiffusionEngine

    stats = {}
    for tag, sent in (("guard_off", False), ("guard_on", True)):
        factory, _ = make_sampler_factory(arch, shapes, params,
                                          sentinel=sent)
        eng = DiffusionEngine(sampler_factory=factory, max_batch=4,
                              max_wait_s=0.02,
                              guardrail=True if sent else None)
        eng.start()
        _drive(eng, traffic)  # warm
        # best-of-2 measured passes: scheduling noise on a serial CPU
        # device dwarfs the sentinels' cost, and the min is the stable
        # statistic for an overhead comparison
        passes = [_drive(eng, traffic)[0] for _ in range(2)]
        eng.stop()
        stats[tag] = min(passes, key=lambda l: np.percentile(l, 50))
    p50_off = np.percentile(stats["guard_off"], 50)
    p50_on = np.percentile(stats["guard_on"], 50)
    overhead = (p50_on - p50_off) / max(p50_off, 1e-9)
    derived = f"overhead_pct={overhead * 100:.2f}"
    for tag in ("guard_off", "guard_on"):
        lat = stats[tag]
        rows += [
            f"serve_mixed[{tag}_p50],{np.percentile(lat, 50) * 1e6:.0f},"
            f"{derived}",
            f"serve_mixed[{tag}_p95],{np.percentile(lat, 95) * 1e6:.0f},"
            f"{derived}",
        ]
    rows += [f"serve_mixed[guardrail_overhead],{overhead * 100:.2f},"
             f"p50_off_us={p50_off * 1e6:.0f};p50_on_us={p50_on * 1e6:.0f}"]


def _journal_section(arch, shapes, params, args, rows):
    """Durability overhead (DESIGN.md §18): the same steady-state
    streaming stream with the request journal + chunk-boundary
    checkpoints off vs on.  The acceptance bar is <5% on p50 — one
    framed JSON record plus one bounded checkpoint file per delivered
    chunk, written outside the engine lock."""
    import tempfile

    from repro.serving import journal as journal_lib
    from repro.serving.engine import DiffusionEngine

    factory, _ = make_sampler_factory(arch, shapes, params)
    stats, jm = {}, {}
    with tempfile.TemporaryDirectory(prefix="serve-mixed-journal-") as td:
        for tag in ("journal_off", "journal_on"):
            traffic = mixed_request_stream(arch, shapes, args.requests,
                                           stream_every=args.stream_every)
            journal = None
            kw = {}
            if tag == "journal_on":
                journal = journal_lib.Journal(os.path.join(td, "j"),
                                              fsync="always")
                kw = dict(journal=journal,
                          checkpoint_store=journal_lib.CheckpointStore(
                              os.path.join(td, "j", "ckpt")))
            eng = DiffusionEngine(sampler_factory=factory, max_batch=4,
                                  max_wait_s=0.02, **kw)
            eng.start()
            _drive(eng, traffic)  # warm
            # best-of-2, same rationale as the guardrail section: the
            # min is the stable statistic on a noisy serial device
            passes = [_drive(eng, traffic)[0] for _ in range(2)]
            jm[tag] = eng.metrics()
            eng.stop()
            if journal is not None:
                journal.close(clean=True)
            stats[tag] = min(passes, key=lambda l: np.percentile(l, 50))
    p50_off = np.percentile(stats["journal_off"], 50)
    p50_on = np.percentile(stats["journal_on"], 50)
    overhead = (p50_on - p50_off) / max(p50_off, 1e-9)
    derived = f"overhead_pct={overhead * 100:.2f}"
    for tag in ("journal_off", "journal_on"):
        lat = stats[tag]
        rows += [
            f"serve_mixed[{tag}_p50],{np.percentile(lat, 50) * 1e6:.0f},"
            f"{derived}",
            f"serve_mixed[{tag}_p95],{np.percentile(lat, 95) * 1e6:.0f},"
            f"{derived}",
        ]
    on = jm["journal_on"]
    rows += [f"serve_mixed[journal_overhead],{overhead * 100:.2f},"
             f"p50_off_us={p50_off * 1e6:.0f};p50_on_us={p50_on * 1e6:.0f};"
             f"journal_fsync_ms={on.get('journal_fsync_ms', 0)};"
             f"checkpoint_write_ms={on.get('checkpoint_write_ms', 0)};"
             f"checkpoint_bytes={on.get('checkpoint_bytes', 0)}"]


def _restart_drill(arch, shapes, params, args):
    """Crash-restart drill (DESIGN.md §18) — the in-process analogue of
    serve.py's ``crash`` fault (which SIGKILLs the whole process; a
    benchmark cannot survive that, so this drill reproduces the exact
    *disk state* in one process): journaled streaming traffic, a
    snapshot of the journal directory at the instant a chunk checkpoint
    lands (precisely what a SIGKILL mid-generation leaves behind — a
    journal with no clean-shutdown marker, submitted-but-unfinished
    requests, and their chunk checkpoints), then journal recovery +
    mid-flight resume into a fresh engine.  Every journaled request
    must complete and at least one must resume from a step > 0."""
    import shutil
    import tempfile

    from repro.serving import journal as journal_lib
    from repro.serving.engine import DiffusionEngine

    factory, _ = make_sampler_factory(arch, shapes, params)
    with tempfile.TemporaryDirectory(prefix="serve-mixed-crash-") as td:
        live = os.path.join(td, "live")
        journal = journal_lib.Journal(live, fsync="always")
        store = journal_lib.CheckpointStore(os.path.join(live, "ckpt"))
        eng = DiffusionEngine(sampler_factory=factory, max_batch=4,
                              max_wait_s=0.02, journal=journal,
                              checkpoint_store=store)
        eng.start()
        traffic = mixed_request_stream(arch, shapes, args.requests,
                                       stream_every=1)
        for _, req in traffic:
            eng.submit(req)
        # "Mid-generation" made deterministic (faults.py crash spec,
        # wait_ckpt): wait for an in-flight chunk checkpoint — entries
        # are discarded at finish, so count>0 means resumable work.
        deadline = time.time() + 120.0
        while store.count() == 0 and time.time() < deadline:
            time.sleep(0.005)
        ckpts_at_crash = store.count()
        # The "crash": freeze the durable state mid-generation.  A
        # concurrent append may leave a torn final frame in the copy —
        # recovery is specified to tolerate exactly that.
        crashed = os.path.join(td, "crashed")
        shutil.copytree(live, crashed)
        eng.stop(drain=False)
        journal_metrics = eng.metrics()
        journal.close(clean=True)  # the live dir is done; drill uses the copy

        rec = journal_lib.recover(crashed)
        # Restart against the crash snapshot: a fresh journal handle
        # (detects the missing clean marker, truncates any torn tail)
        # + the surviving checkpoint store.
        journal2 = journal_lib.Journal(crashed, fsync="always")
        store2 = journal_lib.CheckpointStore(os.path.join(crashed, "ckpt"))
        eng2 = DiffusionEngine(sampler_factory=factory, max_batch=4,
                               max_wait_s=0.02, journal=journal2,
                               checkpoint_store=store2)
        eng2.start()
        resubmitted = []
        for rid in sorted(rec.pending):
            req = journal_lib.request_from_dict(rec.pending[rid])
            req.deadline_s = None  # absolute deadline predates the crash
            req.recovered = True
            ck = store2.get(rid)
            if (ck and req.stream_every
                    and 0 < ck["step"] < req.steps
                    and ck["step"] % req.stream_every == 0):
                req.resume = {"step": ck["step"], "x": ck["x"],
                              "dstate": ck.get("dstate")}
            eng2.submit(req)
            resubmitted.append(rid)
        completed, errors = 0, []
        for rid in resubmitted:
            try:
                eng2.result(rid, timeout=600)
                completed += 1
            except Exception as e:  # noqa: BLE001 — the drill reports
                errors.append(f"{rid}: {e!r}")
        m = eng2.metrics()
        eng2.stop()
        journal2.close(clean=True)
    return {
        "requests": len(traffic),
        "crash_clean_shutdown": rec.clean,        # must be False
        "checkpoints_at_crash": ckpts_at_crash,
        "journal_pending": len(rec.pending),
        "journal_finished_before_crash": len(rec.finished),
        "recovered_count": int(m.get("recovered_count", 0)),
        "resumed_count": int(m.get("resumed_count", 0)),
        "resumed_from_step": int(m.get("last_resume_step", 0)),
        "completed_after_restart": completed,
        "journal_fsync_ms": journal_metrics.get("journal_fsync_ms", 0),
        "checkpoint_write_ms":
            journal_metrics.get("checkpoint_write_ms", 0),
        "checkpoint_bytes": journal_metrics.get("checkpoint_bytes", 0),
        "errors": errors,
    }


def _chaos_section(arch, shapes, params, args):
    """Chaos drill (DESIGN.md §17.3): serve the stream through a
    2+-replica router with the guardrail ladder and a chunk-boundary
    checkpoint store (§18) shared across replicas, the requested faults
    armed; a ``kill_replica`` fault waits for an in-flight request's
    chunk checkpoint to land, then kills the replica serving it — so
    failover demonstrably *resumes* mid-generation instead of replaying
    from step 0.  Every request must still complete.  Runs *instead of*
    the perf sections — armed faults would corrupt their numbers."""
    import tempfile

    from repro.core.guardrail import DegradationLadder
    from repro.serving import faults as fault_lib
    from repro.serving import journal as journal_lib
    from repro.serving.engine import DiffusionEngine
    from repro.serving.router import Router

    fault_lib.install_faults(args.inject_faults)
    fault = fault_lib.active_faults()
    ladder = DegradationLadder()
    factory, _ = make_sampler_factory(arch, shapes, params, sentinel=True)
    replicas = max(args.router_replicas, 2)
    with tempfile.TemporaryDirectory(prefix="serve-mixed-chaos-") as td:
        store = journal_lib.CheckpointStore(os.path.join(td, "ckpt"))
        router = Router(
            [DiffusionEngine(sampler_factory=factory, max_batch=4,
                             max_wait_s=0.02, guardrail=ladder,
                             checkpoint_store=store)
             for _ in range(replicas)],
            probe_interval_s=0.25, checkpoint_store=store)
        router.start()
        traffic = mixed_request_stream(arch, shapes, args.requests,
                                       stream_every=1)
        for _, req in traffic:
            router.submit(req)
        if (fault is not None and fault.spec("kill_replica") is not None
                and fault.take("kill_replica") is not None):
            # Checkpoint entries are discarded at finish, so any rid in
            # the store is in-flight past >=1 chunk boundary: kill the
            # replica serving one of them so its requeue resumes.
            idx, rid = None, None
            deadline = time.time() + 120.0
            while idx is None and time.time() < deadline:
                for r in store.rids():
                    owner = router._assigned.get(r)
                    if owner is not None:
                        idx, rid = owner, r
                        break
                else:
                    time.sleep(0.005)
            if idx is None:  # no checkpoint landed: old deepest-kill
                depths = router.depths()
                idx = max(depths, key=depths.get)
            print(f"# chaos: killing replica {idx} (checkpointed "
                  f"request {rid})", file=sys.stderr)
            router.fail_replica(idx)
        completed = degraded = 0
        errors = []
        for _, req in traffic:
            try:
                r = router.result(req.request_id, timeout=600)
                completed += 1
                degraded += int(r.degraded)
            except Exception as e:  # noqa: BLE001 — reports, not raises
                errors.append(f"{req.request_id}: {e!r}")
        m = router.metrics()
        router.stop()
    counters = dict(fault.counters()) if fault is not None else {}
    fault_lib.clear_faults()
    lm = ladder.metrics()
    return {
        "requests": len(traffic),
        "completed": completed,
        "degraded_count": degraded,
        "failover_count": m["router_requeued"],
        "resumed_count": m["router_resumed"],
        "resumed_from_step": m["router_resumed_from_step"],
        "dense_fallbacks": lm["dense_fallbacks"],
        "ladder": lm,
        "fault_counters": counters,
        "errors": errors,
    }


def main(argv=()) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=REQUESTS)
    ap.add_argument("--deadline-ms", type=float, default=2000.0,
                    help="relative SLO stamped on every request at "
                         "submit in the scheduler/router sections")
    ap.add_argument("--stream-every", type=int, default=1, metavar="K",
                    help="chunked streaming cadence for the SLO "
                         "sections (TTFF is measured per chunk)")
    ap.add_argument("--router-replicas", type=int, default=0, metavar="N",
                    help="also run the Router section over N engine "
                         "replicas (0 = skip)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH-style record of the rows")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="fault spec (see repro.serving.faults); also "
                         "read from $REPRO_FAULTS.  When set, the chaos "
                         "drill runs instead of the perf sections")
    args = ap.parse_args(list(argv))
    if args.inject_faults is None:
        args.inject_faults = os.environ.get("REPRO_FAULTS", "").strip() or None

    arch = get_smoke_config("vdit-paper")
    shapes = mixed_gen_shapes(arch, smoke=True)
    params = init_params(model_fns(arch), jax.random.PRNGKey(0))
    traffic = mixed_request_stream(arch, shapes, args.requests)

    t0 = time.perf_counter()
    rows = []
    chaos = None
    if args.inject_faults:
        from repro.serving import faults as fault_lib

        plan = fault_lib.parse_faults(args.inject_faults)
        if plan.spec("crash") is not None:
            # The crash fault cannot SIGKILL a benchmark that must
            # report afterwards: it selects the in-process restart
            # drill instead (serve.py hosts the real SIGKILL variant).
            chaos = _restart_drill(arch, shapes, params, args)
            rows += [f"serve_mixed[crash_recovered],"
                     f"{chaos['recovered_count']},"
                     f"resumed_from_step={chaos['resumed_from_step']};"
                     f"completed={chaos['completed_after_restart']};"
                     f"pending={chaos['journal_pending']};"
                     f"requests={chaos['requests']}"]
        else:
            chaos = _chaos_section(arch, shapes, params, args)
            rows += [f"serve_mixed[chaos_completed],{chaos['completed']},"
                     f"degraded={chaos['degraded_count']};"
                     f"failover={chaos['failover_count']};"
                     f"resumed={chaos['resumed_count']};"
                     f"resumed_from_step={chaos['resumed_from_step']};"
                     f"requests={chaos['requests']}"]
    else:
        _bucketed_vs_single(arch, shapes, params, traffic, rows)
        _scheduler_section(arch, shapes, params, args, rows)
        _guardrail_section(arch, shapes, params, traffic, rows)
        _journal_section(arch, shapes, params, args, rows)
        if args.router_replicas > 0:
            _router_section(arch, shapes, params, args, rows)

    for row in rows:
        print(row)

    if args.json:
        from benchmarks.run import _parse_rows

        record = {
            "schema": "repro-bench/1",
            "created_unix": round(time.time(), 3),
            "args": {"requests": args.requests,
                     "deadline_ms": args.deadline_ms,
                     "stream_every": args.stream_every,
                     "router_replicas": args.router_replicas,
                     "inject_faults": args.inject_faults},
            "walltime_s": round(time.perf_counter() - t0, 3),
            "benchmarks": _parse_rows("\n".join(rows)),
            "failures": [],
        }
        if chaos is not None:
            record["chaos"] = chaos
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json} ({len(rows)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main(sys.argv[1:])
