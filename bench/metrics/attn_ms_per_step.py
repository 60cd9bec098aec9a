"""Device time of the attention kernels (ripple, flash and block-sparse
Pallas kernels, by name) per batch-step, in ms; nothing where none ran."""


def read(summary, run):
    s = summary.kernel_s.get("attention")
    if not s or not run["batch_steps"]:
        return None
    return 1e3 * s / run["chips"] / run["batch_steps"]
