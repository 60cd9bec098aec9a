"""The reduction of scopes, engine spans and counters (``bench/phases.py``)
and the five metrics that read it: on hand-made operations and spans, as
``test_trace.py`` does, and on a small trace recorded on the CPU."""

import dataclasses
import importlib.util
import os

import pytest

from bench import phases as P
from bench import trace as T

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name}", os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _op(name, start, dur, scope=None, dev="/device:TPU:0"):
    return P.ScopedOp(name, start, dur, dev, scope)


def _span(name, start, dur, **args):
    return P.ArgSpan(name, start, dur, "python3", args)


def test_outermost_scope():
    assert P.scope_of("jit(chunk_fn)/while/body/attn/mlp/dot") == "attn"
    assert P.scope_of("jit(chunk_fn)/while/body/sampler/add") == "sampler"
    assert P.scope_of("jit(chunk_fn)/while/body/dot_general") is None
    assert P.scope_of("") is None


@pytest.fixture
def window():
    """A window from 1 to 11 s holding two batch-steps.

    Device: a ``while`` loop from 0 to 12 around ops of scope mlp (1–3),
    proj (3–4), an attention kernel (4–6) and an unscoped fusion (6–6.5);
    then idle from 6.5 to 8, busy again (mlp) from 8 to 10, idle to 11.
    The ``while`` covers the gaps, so the device's busy time counts only
    the ops inside it here.

    Host: the step's publish span began at 0.5, before the window; the
    second starts inside it.  ``engine.copy_out`` covers 7.25–9 (half of
    the 6.5–8 gap); ``engine.wait`` 6.5–7.25; nothing after 10.
    """
    ops = [_op("fusion.1", 1.0, 2.0, "mlp"), _op("fusion.2", 3.0, 1.0, "proj"),
           _op("ripple_attention_pallas.8", 4.0, 2.0, "attn"),
           _op("fusion.3", 6.0, 0.5), _op("fusion.1", 8.0, 2.0, "mlp")]
    spans = [_span("bench.window", 1.0, 10.0),
             _span("engine.publish", 0.5, 1.0, batch=0, step=3,
                   q_snap=0.5, k_snap=0.5, paper_savings=0.7, skipped=0.1),
             _span("engine.wait", 6.5, 0.75, batch=0, step=4),
             _span("engine.copy_out", 7.25, 1.75, batch=0, step=4),
             _span("engine.publish", 9.0, 0.5, batch=0, step=4,
                   q_snap=0.25, k_snap=0.75, paper_savings=0.3,
                   skipped=0.0)]
    return ops, spans


def test_scopes_spans_idle_and_counters(window):
    ops, spans = window
    s = P.summarize(ops, spans, (1.0, 11.0))
    assert s.scope_s == pytest.approx({"mlp": 4.0, "proj": 1.0,
                                       "attn": 2.0})
    # A window edge clips the publish span that began before it, and only
    # the one that starts inside is the window's batch-step.
    assert s.span_s == pytest.approx({"engine.publish": 0.5 + 0.5,
                                      "engine.wait": 0.75,
                                      "engine.copy_out": 1.75})
    assert [c["step"] for c in s.counters] == [4]
    under = s.idle_under["/device:TPU:0"]
    assert under == pytest.approx({"engine.wait": 0.75,
                                   "engine.copy_out": 0.75,
                                   P.NO_SPAN: 1.0})
    # The base summary is the one trace.summarize gives.
    assert s.busy_s == T.summarize(ops, spans, (1.0, 11.0)).busy_s


def test_container_spans_its_ops_but_has_no_scope_time(window):
    """An op scoped ``mlp`` inside a ``while``: the loop counts towards
    busy time and fills the gaps, but only the op towards its scope."""
    ops, spans = window
    ops = [_op("while.9", 0.0, 12.0, "mlp")] + ops
    s = P.summarize(ops, spans, (1.0, 11.0))
    assert s.scope_s["mlp"] == pytest.approx(4.0)
    assert s.busy_s["/device:TPU:0"] == pytest.approx(10.0)
    assert s.idle_under["/device:TPU:0"] == {}
    cov = P.coverage(ops, s)
    assert cov["busy_named_share"] == pytest.approx(7.0 / 7.5)
    assert cov["idle_under_engine_share"] is None


def test_innermost_engine_span_takes_the_idle():
    """Nested engine spans: a gap goes to the latest-started one."""
    ops = [_op("fusion.1", 0.0, 1.0), _op("fusion.1", 3.0, 1.0)]
    spans = [_span("engine.finish", 0.5, 3.0, batch=0, step=0),
             _span("engine.publish", 1.5, 1.0, batch=0, step=0)]
    s = P.summarize(ops, spans, (0.0, 4.0))
    assert s.idle_under["/device:TPU:0"] == pytest.approx(
        {"engine.finish": 1.0, "engine.publish": 1.0})


def test_readers(window):
    ops, spans = window
    s = P.summarize(ops, spans, (1.0, 11.0))
    run = {"chips": 1, "batch_steps": len(s.counters)}
    assert _reader("proj_mlp_ms_per_step")(s, run) == pytest.approx(5000.0)
    # Idle under every engine span but engine.wait: half the gap.
    assert _reader("engine_idle_ms_per_step")(s, run) == pytest.approx(750.0)
    assert _reader("copy_out_ms_per_step")(s, run) == pytest.approx(1750.0)
    assert _reader("paper_savings")(s, run) == pytest.approx(30.0)
    assert _reader("skipped_attn_share")(s, run) == pytest.approx(0.0)


def test_readers_find_nothing_without_spans_or_scopes(window):
    """On a program with no scopes, spans or counters (and on the plain
    ``trace.Summary``) every reader returns nothing, without raising."""
    ops, spans = window
    bare = [dataclasses.replace(o, scope=None) for o in ops]
    windows = [s for s in spans if s.name == "bench.window"]
    run = {"chips": 1, "batch_steps": 2}
    for s in (P.summarize(bare, windows, (1.0, 11.0)),
              T.summarize(ops, windows, (1.0, 11.0))):
        for name in P.METRICS:
            assert _reader(name)(s, run) is None, name


def test_recorded_trace_carries_scopes_and_span_args(tmp_path):
    """A CPU trace: each op's name stack comes from the HLO module the
    trace keeps, and span arguments from the span's statistics."""
    import jax
    import jax.numpy as jnp

    def step(x):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ x)
        with jax.named_scope("proj"):
            return h @ x

    f = jax.jit(step)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("engine.publish", batch=1,
                                          step=2, q_snap=0.25):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    programs = P.hlo_op_names(path)
    assert any(n.startswith("jit_step(") for n in programs)
    ops, spans = P.read(path)
    assert {o.scope for o in ops} >= {"mlp", "proj"}
    pub = [s for s in spans if s.name == "engine.publish"]
    assert pub and pub[0].args == {"batch": 1, "step": 2, "q_snap": 0.25}
    win = next(s for s in spans if s.name == T.WINDOW_SPAN)
    s = P.summarize(ops, spans, (win.start, win.end))
    assert s.scope_s["mlp"] > 0 and s.counters[0]["q_snap"] == 0.25
