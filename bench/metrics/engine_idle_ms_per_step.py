"""Device idle time under the engine's host phases (every ``engine.*``
span but ``engine.wait``) on the idlest chip, per batch-step, in ms;
nothing where the trace carries no engine spans."""


def read(summary, run):
    idle_under = getattr(summary, "idle_under", None)
    if not idle_under or not getattr(summary, "span_s", None) \
            or not run["batch_steps"]:
        return None
    under = idle_under.get(summary.idlest_device(), {})
    s = sum(v for k, v in under.items()
            if k.startswith("engine.") and k != "engine.wait")
    return 1e3 * s / run["batch_steps"]
