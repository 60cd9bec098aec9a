"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Device operations are the events on the ``XLA Ops`` lines of the
``/device:*`` planes.  A CPU trace has no device planes; there the events
that carry an ``hlo_op`` statistic (XLA's CPU executable) stand in, which
is what the committed test trace exercises.  Host spans are the events of
the ``/host:CPU`` plane, the benchmark's ``bench.*`` annotations among
them.  Every time is in seconds on the profiler's clock.

On a TPU an operation's event name is its whole HLO instruction
(``%ripple_attention_pallas.8 = (bf16[...]) custom-call(...)``); it is
shortened to the instruction's name (``ripple_attention_pallas.8``).  A
Pallas kernel's instruction is named after the jitted function that
calls it.  Kernels are matched by those names; the patterns live in
``KERNELS`` and nowhere else.  Control-flow instructions (``while``,
``conditional``, ``call``) span the operations inside them: they count
towards busy time, which is a union, but not towards the time per
operation.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

# Layer -> regular expression over an operation's instruction name.
KERNELS = {
    "attention": re.compile(
        r"^(ripple_attention_pallas|flash_attention|sparse_attention_pallas)"
        r"(\.\d+)?$"),
    "reuse_check": re.compile(r"^(fused_reuse_snap|reuse_snap)(\.\d+)?$"),
    "collective": re.compile(
        r"^(all-gather|all-reduce|reduce-scatter|collective-permute|"
        r"all-to-all)"),
}
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Op:
    name: str    # the instruction's name, e.g. ``fusion.242``
    start: float
    dur: float
    device: str


def short_name(event_name: str) -> str:
    """``%fusion.2 = bf16[...] fusion(...)`` -> ``fusion.2``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


@dataclasses.dataclass
class Span:
    name: str
    start: float
    dur: float
    thread: str

    @property
    def end(self) -> float:
        return self.start + self.dur


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:  # noqa: BLE001 — a stat the reader cannot decode
        return {}


def read(path: str) -> Tuple[List[Op], List[Span]]:
    """Device operations and host spans of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    device_planes = {p.name for p in pd.planes
                     if p.name.startswith("/device:")}
    for plane in pd.planes:
        on_device = plane.name in device_planes
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if on_device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                st = _stats(ev)
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if on_device or (not device_planes and "hlo_op" in st):
                    dev = plane.name if on_device else \
                        f"cpu:{st.get('device_ordinal', 0)}"
                    ops.append(Op(short_name(ev.name), start, dur, dev))
                elif not on_device:
                    spans.append(Span(ev.name, start, dur, line.name))
    return ops, spans


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    window: Tuple[float, float]
    busy_s: Dict[str, float]          # per device, inside the window
    kernel_s: Dict[str, float]        # per KERNELS layer, all devices
    op_s: Dict[str, float]            # per operation name, all devices
    gaps: List[Tuple[str, float]]     # longest idle gaps, (host span, s)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def max_idle_share(self) -> Optional[float]:
        if not self.busy_s or self.window_s <= 0:
            return None
        return max(1.0 - b / self.window_s for b in self.busy_s.values())


def _host_label(spans: List[Span], t: float) -> str:
    """The innermost host span around ``t``: the latest-started one that
    covers it, the window's own span excepted."""
    best = None
    for s in spans:
        if s.name != WINDOW_SPAN and s.start <= t <= s.end:
            if best is None or s.start > best.start:
                best = s
    return best.name if best is not None else "no host span"


def summarize(ops: List[Op], spans: List[Span],
              window: Tuple[float, float], top: int = 10) -> Summary:
    """Clip every device operation to ``window`` and add it up."""
    lo, hi = window
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    kernel_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    for op in ops:
        s, e = max(op.start, lo), min(op.start + op.dur, hi)
        if e <= s:
            continue
        per_dev[op.device].append((s, e))
        if CONTAINERS.match(op.name):
            continue
        op_s[op.name] += e - s
        for layer, pat in KERNELS.items():
            if pat.match(op.name):
                kernel_s[layer] += e - s
    busy, raw = {}, []
    for dev, iv in per_dev.items():
        u = _union(iv)
        busy[dev] = sum(e - s for s, e in u)
        edges = [lo] + [x for se in u for x in se] + [hi]
        raw += [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
    raw.sort(key=lambda g: g[0] - g[1])
    gaps = [(_host_label(spans, (g0 + g1) / 2), g1 - g0)
            for g0, g1 in raw[:top]]
    return Summary(window, busy, dict(kernel_s), dict(op_s), gaps)


def breakdown(summary: Summary, top: int = 10) -> Dict[str, list]:
    """The ``breakdown`` of a traced run's result line: the device
    operations that took most time, and the longest idle gaps."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:top]]}
