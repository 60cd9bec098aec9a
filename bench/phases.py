#!/usr/bin/env python3
"""The step's named scopes, the engine's phases and the reuse counters,
read from a profiler trace: what ``proj_mlp_ms_per_step``,
``engine_idle_ms_per_step``, ``copy_out_ms_per_step``, ``paper_savings``
and ``skipped_attn_share`` (``bench/metrics/``) read.

    python3 bench/phases.py <trace dir>

reads a trace kept by ``bench/run.py --trace 1 --trace-dir <dir>`` and
prints one JSON object: the five metrics over the window (from the first
``engine.publish`` inside the window span to the last, as the harness
takes the first and last landing), how much device busy time falls
under a scope or a kernel pattern, and how much idle time under an
engine span.

Where each piece lives in the trace (TPU v5e, JAX 0.9):

- A device operation's name stack is not on its ``XLA Ops`` event, which
  carries only its name and times.  It is in the HLO module the trace
  keeps for each program: the ``/host:metadata`` plane holds one ``Hlo
  Proto`` per program, keyed ``<module>(<program id>)``, in which every
  instruction has ``metadata.op_name`` (``jit(chunk_fn)/while/body/.../
  mlp/dot_general``).  JAX's ``ProfileData`` does not expose it, so
  :func:`hlo_op_names` reads the protobuf wire format itself.  An
  operation belongs to the program whose event on the device's ``XLA
  Modules`` line covers it (on a CPU trace: its ``hlo_module`` and
  ``program_id`` statistics).
- The engine's spans (``engine.*``) are host events of the engine
  thread; their arguments (batch, step, the counters on
  ``engine.publish``) are event statistics.

``SCOPES`` lists the scopes; an operation takes the outermost of them in
its name stack.  Kernels keep their own table, ``trace.KERNELS``.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as trace_lib  # noqa: E402

# The named scopes of the served step (models/vdit.py, models/mmdit.py,
# models/attention.py, diffusion/sampler.py).
SCOPES = ("embed", "ada", "proj", "attn", "mlp", "final", "sampler")
ENGINE = "engine."
PUBLISH = "engine.publish"
COUNTERS = ("q_snap", "k_snap", "paper_savings", "skipped")
NO_SPAN = "no engine span"
METRICS = ("proj_mlp_ms_per_step", "engine_idle_ms_per_step",
           "copy_out_ms_per_step", "paper_savings", "skipped_attn_share")


@dataclasses.dataclass
class ScopedOp(trace_lib.Op):
    scope: Optional[str] = None   # outermost SCOPES entry, or None


@dataclasses.dataclass
class ArgSpan(trace_lib.Span):
    args: Dict[str, object] = dataclasses.field(default_factory=dict)


def scope_of(op_name: str) -> Optional[str]:
    """The outermost ``SCOPES`` entry in a '/'-separated name stack."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return None


# -- the protobuf wire format, as far as the HLO metadata needs it ----------


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: bytes for a
    length-delimited field, an int for a varint, skipped otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield num, val


def _ints(val) -> List[int]:
    """A repeated integer field's values: one varint, or a packed run."""
    if isinstance(val, int):
        return [val]
    out, i = [], 0
    while i < len(val):
        v, i = _varint(val, i)
        out.append(v)
    return out


def _instructions(hlo_proto) -> Dict[str, str]:
    """Instruction name -> ``metadata.op_name`` of one ``HloProto``
    (hlo_module = 1; computations = 3, each with id = 5 and instructions
    = 2; an instruction's name = 1, metadata = 7 with op_name = 2, and
    called_computation_ids = 38).  A fusion that carries no op_name of
    its own (XLA leaves multi-output fusions bare) takes the first one
    with a scope among the instructions of the computations it calls."""
    comps: Dict[int, List[str]] = {}
    insts = []   # (name, op_name, called computation ids)
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            cid, names = None, []
            for h, inst in _fields(comp):
                if h == 5:
                    cid = inst
                if h != 2:
                    continue
                name, op_name, called = None, "", []
                for k, v in _fields(inst):
                    if k == 1:
                        name = bytes(v).decode()
                    elif k == 7:
                        for m, w in _fields(v):
                            if m == 2:
                                op_name = bytes(w).decode()
                    elif k == 38:
                        called += _ints(v)
                insts.append((name, op_name, called))
                if op_name:
                    names.append(op_name)
            comps[cid] = names
    out = {}
    for name, op_name, called in insts:
        if not op_name:
            inner = [n for c in called for n in comps.get(c, [])]
            op_name = next((n for n in inner if scope_of(n)),
                           inner[0] if inner else "")
        if name and op_name:
            out[name] = op_name
    return out


def hlo_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Program (``<module>(<program id>)``) -> instruction -> op_name,
    from the ``Hlo Proto`` statistics of the ``/host:metadata`` plane
    (XSpace.planes = 1; XPlane name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata name = 2, stats = 5; XStat
    metadata_id = 1, bytes_value = 6)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for f_num, plane in _fields(space):
        if f_num != 1:
            continue
        fields = list(_fields(plane))
        names = [bytes(v).decode() for k, v in fields if k == 2]
        if names != ["/host:metadata"]:
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:
                sid = sname = None
                for e, v in _fields(entry):
                    if e == 2:
                        for m, w in _fields(v):
                            if m == 1:
                                sid = w
                            elif m == 2:
                                sname = bytes(w).decode()
                stat_names[sid] = sname
        for k, entry in fields:
            if k != 4:
                continue
            for e, meta in _fields(entry):
                if e != 2:
                    continue
                prog, proto = None, None
                for m, w in _fields(meta):
                    if m == 2:
                        prog = bytes(w).decode()
                    elif m == 5:
                        stat = dict(_fields(w))
                        if stat_names.get(stat.get(1)) == "Hlo Proto":
                            proto = stat.get(6)
                if prog and proto is not None:
                    out[prog] = _instructions(proto)
    return out


# -- reading -----------------------------------------------------------------


def read(path: str) -> Tuple[List[ScopedOp], List[ArgSpan]]:
    """Device operations with their scope, and host spans with their
    arguments, of one trace file (the events ``trace.read`` reads)."""
    from jax.profiler import ProfileData

    programs = hlo_op_names(path)
    pd = ProfileData.from_file(path)
    ops: List[ScopedOp] = []
    spans: List[ArgSpan] = []
    device_planes = {p.name for p in pd.planes
                     if p.name.startswith("/device:")}
    for plane in pd.planes:
        on_device = plane.name in device_planes
        if not on_device and not plane.name.startswith("/host:"):
            continue
        # A device op's program: the XLA Modules event that covers it.
        mods = []
        if on_device:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = sorted((ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                                   ev.name) for ev in line.events)
        starts = [m[0] for m in mods]
        for line in plane.lines:
            if on_device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                st = trace_lib._stats(ev)
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if on_device or (not device_planes and "hlo_op" in st):
                    name = trace_lib.short_name(ev.name)
                    if on_device:
                        dev = plane.name
                        j = bisect.bisect_right(starts, start) - 1
                        prog = mods[j][2] if j >= 0 and \
                            start <= mods[j][0] + mods[j][1] else None
                    else:
                        dev = f"cpu:{st.get('device_ordinal', 0)}"
                        prog = f"{st.get('hlo_module')}({st.get('program_id')})"
                    op_name = programs.get(prog, {}).get(name, "")
                    ops.append(ScopedOp(name, start, dur, dev,
                                        scope_of(op_name)))
                elif not on_device:
                    spans.append(ArgSpan(ev.name, start, dur, line.name,
                                         st))
    return ops, spans


# -- reduction -----------------------------------------------------------------


@dataclasses.dataclass
class PhaseSummary(trace_lib.Summary):
    scope_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    span_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    # device -> innermost engine span (or NO_SPAN) -> idle seconds
    idle_under: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    counters: List[Dict[str, object]] = dataclasses.field(
        default_factory=list)

    def idlest_device(self) -> Optional[str]:
        if not self.busy_s:
            return None
        return min(self.busy_s, key=self.busy_s.get)


def _labelled(spans: List[ArgSpan], lo: float, hi: float):
    """``[(start, end, name)]`` covering ``[lo, hi]``: each piece labelled
    by the innermost (latest-started) engine span over it."""
    eng = [s for s in spans if s.name.startswith(ENGINE)]
    cuts = sorted({lo, hi} | {min(max(x, lo), hi) for s in eng
                              for x in (s.start, s.end)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        best = None
        for s in eng:
            if s.start <= mid <= s.end and (best is None
                                            or s.start > best.start):
                best = s
        out.append((a, b, best.name if best else NO_SPAN))
    return out


def summarize(ops: List[ScopedOp], spans: List[ArgSpan],
              window: Tuple[float, float], top: int = 10) -> PhaseSummary:
    """``trace.summarize`` of the window, plus device time per scope,
    host time per engine span, idle time per engine span and the
    counters of the window's ``engine.publish`` spans."""
    base = trace_lib.summarize(ops, spans, window, top)
    lo, hi = window
    scope_s: Dict[str, float] = defaultdict(float)
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for op in ops:
        s, e = max(op.start, lo), min(op.start + op.dur, hi)
        if e <= s:
            continue
        per_dev[op.device].append((s, e))
        if op.scope and not trace_lib.CONTAINERS.match(op.name):
            scope_s[op.scope] += e - s
    span_s: Dict[str, float] = defaultdict(float)
    for sp in spans:
        if sp.name.startswith(ENGINE):
            s, e = max(sp.start, lo), min(sp.end, hi)
            if e > s:
                span_s[sp.name] += e - s
    pieces = _labelled(spans, lo, hi)
    idle_under: Dict[str, Dict[str, float]] = {}
    for dev, iv in per_dev.items():
        busy = trace_lib._union(iv)
        edges = [lo] + [x for se in busy for x in se] + [hi]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        under: Dict[str, float] = defaultdict(float)
        k = 0
        for a, b in gaps:
            while k < len(pieces) and pieces[k][1] <= a:
                k += 1
            j = k
            while j < len(pieces) and pieces[j][0] < b:
                pa, pb, name = pieces[j]
                under[name] += max(0.0, min(b, pb) - max(a, pa))
                j += 1
        idle_under[dev] = dict(under)
    counters = [dict(sp.args) for sp in sorted(spans, key=lambda x: x.start)
                if sp.name == PUBLISH and lo <= sp.start < hi]
    return PhaseSummary(**dataclasses.asdict(base), scope_s=dict(scope_s),
                        span_s=dict(span_s), idle_under=idle_under,
                        counters=counters)


def coverage(ops: List[ScopedOp], summary: PhaseSummary) -> Dict[str, float]:
    """Shares of the window's device busy time (operations, containers
    left out) under a ``SCOPES`` entry or a ``trace.KERNELS`` pattern,
    and of its idle time (idlest device) under an engine span."""
    lo, hi = summary.window
    total = named = 0.0
    for op in ops:
        s, e = max(op.start, lo), min(op.start + op.dur, hi)
        if e <= s or trace_lib.CONTAINERS.match(op.name):
            continue
        total += e - s
        if op.scope or any(p.match(op.name)
                           for p in trace_lib.KERNELS.values()):
            named += e - s
    under = summary.idle_under.get(summary.idlest_device() or "", {})
    idle = sum(under.values())
    return {"busy_named_share": named / total if total else None,
            "idle_under_engine_share":
                (idle - under.get(NO_SPAN, 0.0)) / idle if idle else None}


def _reader(name: str):
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    ops, spans = read(trace_lib.find_xplane(args[0]))
    win = [s for s in spans if s.name == trace_lib.WINDOW_SPAN]
    if not win:
        print("bench: the trace holds no window span", file=sys.stderr)
        return 2
    # The harness's window: from the first batch-step that lands inside
    # the window span to the last (here: their engine.publish spans), so
    # no operation or span cut by the trace's ends is read.
    lo, hi = win[0].start, win[0].end
    pubs = sorted(s.start for s in spans
                  if s.name == PUBLISH and lo <= s.start <= hi)
    if len(pubs) >= 2:
        lo, hi = pubs[0], pubs[-1]
    summary = summarize(ops, spans, (lo, hi))
    run = {"chips": max(len(summary.busy_s), 1),
           "batch_steps": len(summary.counters)}
    metrics = {}
    for name in METRICS:
        metrics[name] = _reader(name).read(summary, run)
    print(json.dumps({
        "metrics": metrics, "run": run, "window_s": summary.window_s,
        "coverage": coverage(ops, summary), "scope_s": summary.scope_s,
        "span_s": summary.span_s, "idle_under": summary.idle_under,
        "kernel_s": summary.kernel_s}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
