"""Share (%) of attention MXU work the executed kernel skipped, the mean
over the window's steps of the ``skipped`` counter on their
``engine.publish`` spans; nothing where no span carries it."""


def read(summary, run):
    vals = [c["skipped"] for c in getattr(summary, "counters", [])
            if "skipped" in c]
    return 100.0 * sum(vals) / len(vals) if vals else None
