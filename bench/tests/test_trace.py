"""The trace reduction, on a small trace recorded once on the CPU and
committed (``data/cpu_window.xplane.pb``, made by :func:`record`), and on
hand-made operations for what a CPU trace cannot show (kernel names,
several chips)."""

import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "cpu_window.xplane.pb")


def record(path=DATA):  # pragma: no cover - how the committed file was made
    """Three steps of a small jitted program inside the benchmark's window
    span, with a host-side pause between steps."""
    import glob
    import shutil
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.stream_wait"):
                f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)[0], path)


@pytest.fixture(scope="module")
def recorded():
    return T.read(DATA)


def test_committed_trace_is_small():
    assert os.path.getsize(DATA) < 1 << 20


def test_reads_ops_and_spans(recorded):
    ops, spans = recorded
    assert ops and all(o.dur >= 0 for o in ops)
    names = {s.name for s in spans}
    assert T.WINDOW_SPAN in names and "bench.stream_wait" in names


def test_window_summary(recorded):
    ops, spans = recorded
    win = next(s for s in spans if s.name == T.WINDOW_SPAN)
    s = T.summarize(ops, spans, (win.start, win.end))
    assert 0 < s.mean_busy_s() <= s.window_s
    # Three 20 ms pauses with nothing on the device.
    assert 0.06 / s.window_s <= s.max_idle_share() < 1
    assert s.gaps and s.gaps[0][1] >= 0.015
    b = T.breakdown(s)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) <= sum(s.op_s.values()) + 1e-9


def _op(name, start, dur, dev="/device:TPU:0"):
    return T.Op(name, start, dur, dev)


def test_instruction_names():
    assert T.short_name("%ripple_attention_pallas.8 = (bf16[24,16512,128]"
                        "{2,1,0}) custom-call(...)") \
        == "ripple_attention_pallas.8"
    assert T.short_name("dot.49") == "dot.49"


def test_clipping_union_and_kernels():
    ops = [_op("while.9", 0.0, 12.0), _op("fusion.1", 0.0, 2.0),
           _op("fusion.2", 1.0, 2.0),
           _op("ripple_attention_pallas.8", 4.0, 1.0),
           _op("fused_reuse_snap.17", 5.5, 0.5),
           _op("all-reduce.3", 7.0, 1.0), _op("fusion.1", 9.0, 3.0),
           _op("fusion.9", 1.0, 1.0, "/device:TPU:1")]
    spans = [T.Span("bench.window", 0.0, 20.0, "python"),
             T.Span("bench.result", 3.0, 1.0, "python")]
    s = T.summarize(ops[1:], spans, (1.0, 10.0))
    assert s.busy_s["/device:TPU:0"] == pytest.approx(2.0 + 1.0 + 0.5
                                                      + 1.0 + 1.0)
    assert s.kernel_s == pytest.approx(
        {"attention": 1.0, "reuse_check": 0.5, "collective": 1.0})
    assert s.max_idle_share() == pytest.approx(1 - 1.0 / 9.0)
    assert s.mean_busy_s() == pytest.approx((5.5 + 1.0) / 2)
    # The second chip idles from 2 to 10, under no host span; the first's
    # gap from 3 to 4 falls inside the result wait.
    assert s.gaps[0] == ("no host span", pytest.approx(8.0))
    assert ("bench.result", pytest.approx(1.0)) in s.gaps
    # A loop spans what runs inside it: busy, but no operation of its own.
    s = T.summarize(ops, spans, (1.0, 10.0))
    assert s.busy_s["/device:TPU:0"] == pytest.approx(9.0)
    assert "while.9" not in s.op_s and s.op_s["fusion.1"] == \
        pytest.approx(2.0)
