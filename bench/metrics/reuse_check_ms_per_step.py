"""Device time of the fused Δ-check kernel (by name) per batch-step, in
ms; nothing where none ran."""


def read(summary, run):
    s = summary.kernel_s.get("reuse_check")
    if not s or not run["batch_steps"]:
        return None
    return 1e3 * s / run["chips"] / run["batch_steps"]
