"""Model FLOP utilization of the whole step (%): the ``dense`` FLOPs of
the configuration's FLOP count (no attention products) times the
request-steps landed in the traced window, over the window, the chips and
the chip's bf16 peak."""


def read(summary, run):
    if not run["peak"] or run["window_s"] <= 0 or not run["request_steps"]:
        return None
    done = run["flops"]["dense"] * run["request_steps"]
    return 100.0 * done / run["window_s"] / (
        run["chips"] * run["peak"]["bf16_flops"])
