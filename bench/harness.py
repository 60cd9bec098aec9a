"""One run of one cell: set-up, the measured window, the comparison, and
the result line.  ``run.py`` is the command; this is what it drives.

Nothing here names a configuration, a traffic mix or a per-layer metric:
each is found by the name ``BENCHMARK.json`` gives it.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import check, weights
from bench import trace as trace_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
RIPPLE_DEFAULTS = dict(snap_q=True, snap_k=True, svg_mask=False,
                       fixed_threshold=None, theta_t=None, theta_x=None,
                       theta_y=None, reuse_every=1, drift_tol=0.0)


class BenchError(Exception):
    """A run that cannot produce a result line."""


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    if not os.path.exists(path):
        raise BenchError(f"no module at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str):
    """(benchmark, cell, configuration file, traffic file) by name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def config_module(config: Dict, kind: str):
    """The configuration's reference (``configs/<name>.py``) or FLOP count
    (``flops/<name>.py``)."""
    sub = "configs" if kind == "reference" else "flops"
    return load_module(os.path.join(BENCH, sub, config["name"] + ".py"),
                       f"bench_{kind}_{config['name'].replace('-', '_')}")


def devices_for(chips: int, require_chip: bool):
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise BenchError(f"platform is {devs[0].platform!r}, not 'tpu'; "
                         "refusing to measure anything else")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def _tuples(v):
    return tuple(v) if isinstance(v, list) else v


def program_arch(config: Dict, traffic: Dict):
    """The program's configuration, cut as the file states, with the
    traffic's ramp and backend.  Every model key the file states must be
    a field of the program's and is applied; fields it does not state
    keep the program's defaults (the comparison with the reference
    catches a default that changes the maths).  Refuses ripple settings
    that differ from what the reference assumes."""
    from repro.configs import get_config

    arch = get_config(config["arch"])
    fields = {f.name for f in dataclasses.fields(arch.model)}
    unknown = sorted(set(config["model"]) - fields)
    if unknown:
        raise BenchError(f"{config['name']}: the program's model has no "
                         f"field {unknown}")
    model = dataclasses.replace(
        arch.model, **{k: _tuples(v) for k, v in config["model"].items()})
    rip = dict(config["ripple"], i_min=traffic["ripple"]["i_min"],
               i_max=traffic["ripple"]["i_max"],
               backend=traffic["ripple"]["backend"])
    ripple = dataclasses.replace(
        arch.ripple, enabled=True,
        **{k: _tuples(v) for k, v in rip.items()})
    for k, v in RIPPLE_DEFAULTS.items():
        if getattr(ripple, k) != v:
            raise BenchError(f"ripple.{k} is {getattr(ripple, k)!r}; the "
                             f"reference assumes {v!r}")
    arch = dataclasses.replace(arch, model=model, ripple=ripple)
    shape = arch.shape(traffic["shape"])
    if shape.img_res != config["model"]["img_res"]:
        raise BenchError(f"shape {shape.name} is {shape.img_res}px, the "
                         f"configuration {config['model']['img_res']}px")
    return arch, dataclasses.replace(shape, steps=traffic["steps"])


def make_params(arch, ref, config: Dict, seed: int):
    """The program's parameter tree, every leaf from ``bench.weights``."""
    from repro.launch.workloads import model_fns
    from repro.models.params import init_params

    abstract = jax.eval_shape(
        lambda: init_params(model_fns(arch), jax.random.PRNGKey(0)))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = ["/".join(str(k.key) for k in path) for path, _ in flat]
    table = ref.param_table(config["model"])
    have = {p: tuple(a.shape) for p, (_, a) in zip(paths, flat)}
    want = {p: tuple(s) for p, (s, _) in table.items()}
    if have != want:
        diff = sorted(set(have.items()) ^ set(want.items()))[:6]
        raise BenchError(f"program and reference parameters differ: {diff}")
    if any(a.dtype != np.float32 for _, a in flat):
        raise BenchError("the program's parameters are not all float32")
    vals = weights.make(table, seed)
    return jax.tree_util.tree_unflatten(treedef, [vals[p] for p in paths])


class Traffic:
    """Requests from the seed: request ``k`` always has the same text
    embeddings and noise seed, whoever submits it."""

    def __init__(self, arch, shape, traffic: Dict, seed: int):
        from repro.launch.workloads import latent_shape_for

        self.m = arch.model
        self.latent_shape = tuple(latent_shape_for(arch, shape))
        self.t = traffic
        self.seed = int(seed) % 2 ** 63
        self.next_id = 0
        self.lock = threading.Lock()
        self.txt: Dict[int, np.ndarray] = {}

    def next(self):
        from repro.serving.engine import GenRequest

        with self.lock:
            k = self.next_id
            self.next_id += 1
        rng = np.random.default_rng([self.seed, 0, k])
        txt = rng.standard_normal((self.m.txt_tokens, self.m.txt_dim),
                                  dtype=np.float32) * self.t["txt_std"]
        self.txt[k] = txt
        return GenRequest(request_id=k, txt=txt, steps=self.t["steps"],
                          seed=int(rng.integers(0, 2 ** 31)),
                          latent_shape=self.latent_shape,
                          policy=self.t["ripple"]["policy"],
                          stream_every=self.t["stream_every"])


class Recorder:
    """Every streamed step: when it landed, and its latents."""

    def __init__(self):
        self.lock = threading.Lock()
        self.landings: List[tuple] = []   # (perf_counter, request, step)
        self.chunks: Dict[int, Dict[int, np.ndarray]] = {}
        self.first = threading.Event()
        self.first_wall = None
        self.attempted = 0
        self.failed: Dict[int, str] = {}

    def fail(self, rid: int, why: str):
        """A request failed; before any landing this ends set-up too, so
        a server that cannot serve is reported at once."""
        with self.lock:
            self.failed[rid] = why
        self.first.set()

    def land(self, rid: int, step: int, lat: np.ndarray):
        t = time.perf_counter()
        with self.lock:
            self.landings.append((t, rid, step))
            self.chunks.setdefault(rid, {})[step] = lat
            if self.first_wall is None:
                self.first_wall = time.time()
                self.first.set()


def client(engine, traffic: Traffic, rec: Recorder, stop: threading.Event,
           first_req):
    """A closed-loop client: stream every step of its request, fetch the
    result, and submit the next request, until the window closes.  The
    next request is made while the current one runs, so the resubmission
    at completion is immediate and batch-mates arrive within the
    engine's linger."""
    req, nxt = first_req, None
    while True:
        rid, n = req.request_id, 0
        try:
            it = engine.stream(rid, timeout=900.0)
            while True:
                with jax.profiler.TraceAnnotation("bench.stream_wait"):
                    lat = next(it, None)
                if lat is None:
                    break
                rec.land(rid, n, lat)
                n += 1
                if nxt is None:
                    nxt = traffic.next()
            with jax.profiler.TraceAnnotation("bench.result"):
                res = engine.result(rid, timeout=900.0)
            with rec.lock:
                rec.attempted += 1
            bad = None
            if n != traffic.t["steps"]:
                bad = f"{n} of {traffic.t['steps']} steps streamed"
            elif res.degraded:
                bad = "degraded by the guardrail ladder"
            elif not np.isfinite(res.latents).all():
                bad = "non-finite latents"
            if bad:
                rec.fail(rid, bad)
        except (RuntimeError, TimeoutError) as e:
            if stop.is_set() and n == 0 and "engine stopped" in str(e):
                return  # queued when the window closed; never started
            with rec.lock:
                rec.attempted += 1
            rec.fail(rid, str(e))
        if stop.is_set():
            return
        req, nxt = nxt or traffic.next(), None
        with jax.profiler.TraceAnnotation("bench.submit"):
            try:
                engine.submit(req)
            except RuntimeError:
                return  # the engine stopped between the check and here


def window_rate(landings, t0: float, seconds: float, tokens: int):
    """Tokens per second between the window's first landing and its last:
    every request-step landed after ``t0`` and by the last landing at or
    before ``t0 + seconds``, over that time."""
    inside = [t for t, _, _ in landings if t0 < t <= t0 + seconds]
    if not inside:
        raise BenchError(f"no step landed within {seconds} s of the first")
    return len(inside) * tokens / (max(inside) - t0), max(inside)


def run_cell(bench: Dict, cell: Dict, config: Dict, traffic: Dict, *,
             seed: int, seconds: float, trace: bool,
             trace_dir: Optional[str] = None, control: bool = False,
             t_start: Optional[float] = None, require_chip: bool = True,
             fault: Optional[Dict] = None) -> Dict:
    """One run of ``cell``; returns the result line's object.  ``fault``
    (tests only) breaks the timed path: its ``"arch"`` maps the program's
    configuration, its ``"factory"`` wraps the sampler factory."""
    fault = fault or {}
    t_start = time.time() if t_start is None else t_start
    devs = devices_for(cell["chips"], require_chip)
    from repro.core.guardrail import DegradationLadder
    from repro.launch.serve import make_sampler_factory
    from repro.serving.engine import DiffusionEngine
    from repro.utils.platform import place_compile_cache

    cache = place_compile_cache()
    ref = config_module(config, "reference")
    flops = config_module(config, "flops")
    peaks = load_json(os.path.join(BENCH, "peaks.json"))
    kind = devs[0].device_kind
    if kind not in peaks and require_chip:
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    for k in config.get("reduced", []):
        say(f"cut: {k} = {config['model'][k]} of "
            f"{config['published'][k]} published")
    say(f"traffic {cell['traffic']}: {json.dumps(traffic)}")
    arch, shape = program_arch(config, traffic)
    if "arch" in fault:
        arch = fault["arch"](arch)
    say(f"compile cache {cache}; device {kind} x{len(devs)}")

    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _d, **_kw: compiles.append(time.perf_counter())
        if name == COMPILE_EVENT else None)

    params = make_params(arch, ref, config, seed)
    factory, plan_fn = make_sampler_factory(
        arch, (shape,), params, use_ripple=True, mesh=None, sentinel=True)
    if "factory" in fault:
        factory = fault["factory"](factory)
    engine = DiffusionEngine(
        sampler_factory=factory, max_batch=traffic["max_batch"],
        max_compiled=8, plan_fn=plan_fn,
        default_policy=traffic["ripple"]["policy"], scheduler="edf",
        guardrail=DegradationLadder())
    gen = Traffic(arch, shape, traffic, seed)
    rec = Recorder()
    stop = threading.Event()
    first = [gen.next() for _ in range(traffic["clients"])]
    for r in first:
        engine.submit(r)  # all queued before the engine starts: one batch
    engine.start()
    clients = [threading.Thread(target=client, daemon=True,
                                args=(engine, gen, rec, stop, r))
               for r in first]
    for c in clients:
        c.start()

    tdir = None
    try:
        if not rec.first.wait(timeout=1200.0):
            raise BenchError("no step landed within 1200 s of start")
        if not rec.landings:
            raise BenchError(f"a request failed before any step landed: "
                             f"{rec.failed}")
        t0 = min(t for t, _, _ in rec.landings)
        setup_s = rec.first_wall - t_start
        if trace:
            tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tdir)
            span = jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN)
            span.__enter__()
            t_span = time.perf_counter()
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        if trace:
            t_span_end = time.perf_counter()
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
    finally:
        stop.set()
        engine.stop(drain=False)  # the batch in flight ends; queue dropped
        for c in clients:
            c.join()

    tok = flops.latent_tokens(config["model"])
    rate, t_last = window_rate(rec.landings, t0, seconds, tok)
    in_win = sum(1 for t in compiles if t0 < t <= t0 + seconds)
    say(f"window {seconds} s: {len(rec.landings)} steps landed in all; "
        f"{rate:.4f} tokens/s; {in_win} compilations inside")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    counters = engine.metrics()

    # Free the server's state before the reference runs.
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    del engine, factory, plan_fn, params
    gc.collect()

    landed = [(t, r, s) for t, r, s in rec.landings if t0 < t <= t_last]
    rip = dict(config["ripple"], **traffic["ripple"])
    samples = check.draw(landed, traffic["steps"], rip,
                         traffic["compare"], seed,
                         traffic.get("compare_steps"))
    t_ref = time.perf_counter()
    worst = check.compare(ref, weights.seed_key(seed), config["model"], rip,
                          traffic["steps"], samples, rec.chunks, gen.txt,
                          control=control)
    ok, checks = check.verdict(worst, config["limits"])
    say(f"reference on {len(samples)} request-step(s) {samples}: "
        f"{time.perf_counter() - t_ref:.1f} s; {worst}")
    if rec.failed:
        say(f"failed requests: {rec.failed}")
    if counters.get("degraded_count", 0):
        say(f"guardrail degraded {counters['degraded_count']} bucket(s)")

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(ok and not rec.failed),
           "attempted": rec.attempted, "failed": len(rec.failed)}
    if trace:
        per_layer, extra = read_trace(
            bench, cell, config, traffic, tdir, rec, flops, peaks.get(kind),
            (t_span, t_span_end), compiles, len(devs))
        device.update(extra["device"])
        out["metrics"] = per_layer
        out["device"] = device
        out["breakdown"] = extra["breakdown"]
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        out["metrics"] = {
            "tokens_per_s": {"value": rate, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        out["device"] = device
    if control:
        out["control"] = {k: v for k, v in worst.items()
                          if k.startswith("control_")}
    out["checks"] = checks
    return out


def read_trace(bench, cell, config, traffic, tdir, rec: Recorder, flops,
               peak, span_host, compiles, chips):
    """Per-layer metrics and the breakdown from the window's trace."""
    ops, spans = trace_lib.read(trace_lib.find_xplane(tdir))
    win = [s for s in spans if s.name == trace_lib.WINDOW_SPAN]
    if not win:
        raise BenchError("the trace holds no window span")
    # Host perf_counter -> profiler clock, from the window span's start.
    off = win[0].start - span_host[0]
    # Step boundaries inside the trace: the batch landings (one batch
    # lands its requests together) from the first to the last.
    inside = [x for x in rec.landings
              if span_host[0] <= x[0] <= span_host[1]]
    lands = sorted(t for t, _, _ in inside)
    batches = [g[0][0] for g in check.batch_steps(inside)]
    if len(batches) < 2:
        raise BenchError("fewer than two batch-steps landed in the trace")
    lo, hi = batches[0], batches[-1]
    summary = trace_lib.summarize(ops, spans, (lo + off, hi + off))
    req_steps = sum(1 for t in lands if lo < t <= hi)
    run = {"chips": chips, "peak": peak,
           "flops": flops.step_flops(config["model"]),
           "request_steps": req_steps, "batch_steps": len(batches) - 1,
           "window_s": hi - lo,
           "compiles_in_window": sum(1 for t in compiles
                                     if span_host[0] <= t <= span_host[1])}
    say(f"trace window {hi - lo:.3f} s: {run['batch_steps']} batch-steps, "
        f"{req_steps} request-steps; busy {summary.busy_s}; kernels "
        f"{summary.kernel_s}")
    metrics = {}
    for m in bench["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        reader = load_module(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"),
                             f"bench_metric_{m['name'].replace('.', '_')}")
        v = reader.read(summary, run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    extra = {"device": {"busy_s": summary.mean_busy_s(),
                        "window_s": summary.window_s},
             "breakdown": trace_lib.breakdown(summary)}
    return metrics, extra


def print_checks(result: Dict):
    """Each compared number beside its limit, as the last lines on
    standard error."""
    for name, c in result["checks"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
