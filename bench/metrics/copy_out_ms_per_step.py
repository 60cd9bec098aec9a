"""Host time of the engine's copy of each step's latents and counters
to the host (the ``engine.copy_out`` span), per batch-step, in ms;
nothing where the trace carries no such span."""


def read(summary, run):
    s = (getattr(summary, "span_s", None) or {}).get("engine.copy_out")
    if s is None or not run["batch_steps"]:
        return None
    return 1e3 * s / run["batch_steps"]
