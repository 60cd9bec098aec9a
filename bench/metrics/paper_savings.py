"""The paper's savings (%): the share of partial attention scores the
reuse path copies instead of computing, the mean over the window's
steps of the ``paper_savings`` counter on their ``engine.publish``
spans; nothing where no span carries it."""


def read(summary, run):
    vals = [c["paper_savings"] for c in getattr(summary, "counters", [])
            if "paper_savings" in c]
    return 100.0 * sum(vals) / len(vals) if vals else None
